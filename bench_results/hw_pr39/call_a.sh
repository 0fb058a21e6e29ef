#!/bin/bash
# Call A (one chip): the parent on the new cell's name (its own manifest, then this PR's
# benchmark laid over it: both must exit at once, not 0); the change on kanana2_train_8k,
# one run untraced and one traced, a seed each. If the untraced run dies (memory), the same
# at 5 layers, to learn the depth in one call.
set -u
OUT=chiprun_out/hw_pr39; mkdir -p $OUT
export JAX_COMPILATION_CACHE_DIR=$PWD/.jax_cache_call_a JAX_COMPILATION_CACHE_MAX_SIZE=-1
CELL=kanana2_train_8k
t0=$(date +%s)
( cd _scratch/parent && python3 benchmark/run.py --workload $CELL --seed 3900000001 --seconds 40 --trace 0 ) > $OUT/a_parent_own.out 2> $OUT/a_parent_own.err
echo "parent, own manifest: rc $? after $(( $(date +%s) - t0 )) s: $(tail -1 $OUT/a_parent_own.err)"
t0=$(date +%s)
rm -rf _scratch/overlay && cp -r _scratch/parent _scratch/overlay && cp BENCHMARK.json _scratch/overlay/ && cp -r benchmark/. _scratch/overlay/benchmark/
( cd _scratch/overlay && python3 benchmark/run.py --workload $CELL --seed 3900000001 --seconds 40 --trace 0 ) > $OUT/a_parent_overlay.out 2> $OUT/a_parent_overlay.err
echo "parent, this PR's benchmark laid over: rc $? after $(( $(date +%s) - t0 )) s: $(tail -1 $OUT/a_parent_overlay.err)"
run() { # name seed trace
  t0=$(date +%s)
  python3 benchmark/run.py --workload $CELL --seed $2 --seconds 40 --trace $3 > $OUT/$1.out 2> $OUT/$1.err
  rc=$?
  echo "$1: rc $rc after $(( $(date +%s) - t0 )) s"
  grep -E "^check |^memory|^train:|^reference:|^routing:" $OUT/$1.out
  tail -1 $OUT/$1.out | cut -c1-3500
  [ $rc -ne 0 ] && tail -5 $OUT/$1.err | cut -c1-1500
  return $rc
}
if run a_change_s101 3900000101 0; then
  run a_change_traced_s102 3900000102 1
  cp .bench_work/$CELL/scope_time.json $OUT/a_scope_time.json 2>/dev/null
  cp .bench_work/$CELL/telemetry.jsonl $OUT/a_telemetry.jsonl 2>/dev/null
  # the other kept set, on the first run's seed: only what ISSUE 39 names (the out-projection,
  # the shared experts' first product and layer 0's gate run again)
  rm -rf _scratch/small_kept && mkdir -p _scratch/small_kept && cp -r BENCHMARK.json benchmark csed_514_project_distributed_training_using_pytorch_tpu _scratch/small_kept/
  python3 - <<'P'
import re
p = "_scratch/small_kept/csed_514_project_distributed_training_using_pytorch_tpu/models/hybrid_lm.py"
s = open(p).read()
s, n = re.subn(r'MLA_KEPT = \([^)]*\)', 'MLA_KEPT = ("flash_out", "flash_lse", "mla_latent", "moe_route", "moe_sort")', s)
assert n == 1
open(p, "w").write(s)
P
  ( cd _scratch/small_kept && OUT=../../$OUT && t0=$(date +%s) && python3 benchmark/run.py --workload $CELL --seed 3900000101 --seconds 40 --trace 0 > $OUT/a_small_kept_s101.out 2> $OUT/a_small_kept_s101.err; echo "a_small_kept_s101: rc $? after $(( $(date +%s) - t0 )) s"; grep -E "^memory|^train:" $OUT/a_small_kept_s101.out; tail -1 $OUT/a_small_kept_s101.out | cut -c1-600 )
else
  python3 - <<'P'
import json
p = "benchmark/configs/kanana-2-30b-a3b-ep8.json"
c = json.load(open(p)); c["num_hidden_layers"] = 5; c["parameters"] = 575955968
json.dump(c, open(p, "w"), indent=1)
P
  run a_change_5layers_s101 3900000101 0 && run a_change_5layers_traced_s102 3900000102 1
  cp .bench_work/$CELL/scope_time.json $OUT/a_scope_time.json 2>/dev/null
  cp .bench_work/$CELL/telemetry.jsonl $OUT/a_telemetry.jsonl 2>/dev/null
fi
