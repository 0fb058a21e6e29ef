#!/bin/bash
# Parent and change in one call, on one chip, sharing one compile cache that starts empty: each
# step runs one cell once from one tree (bench_results/hw_pr40/pairs.sh writing under pr41, and a traced run's time by instruction).
# `change` is the repo as it stands on disk; any other tree is a `git archive` unpacked at
# _scratch/<tree> (parent: the parent commit 08dadd7; final: `git write-tree`).
# usage: pairs.sh <call label> <budget seconds> <step> ...; a step is tree:cell:seed:trace[:cache],
#        cache naming a second compile cache that starts empty too (a tree's own executable after
#        another tree has filled the first)
call=$1; budget=$2; shift 2
repo=/root/repo
out=$repo/chiprun_out/pr48/$call; mkdir -p $out
export JAX_COMPILATION_CACHE_DIR=$repo/.jax_cache_call JAX_COMPILATION_CACHE_MAX_SIZE=-1
mkdir -p $JAX_COMPILATION_CACHE_DIR
start=$(date +%s); n=0
declare -A longest=([kanana2_train_8k]=600 [kimi_linear_train_8k]=480 [nemotron_h_train_8k]=450 [lfm2_moe_train_8k]=360 [lm_train_b16]=200 [evabyte_train_32k]=330 [qwen3_next_train_8k]=420 [falcon_h1_train_8k]=420)
for step in "$@"; do
  IFS=: read tree cell seed trace form cache <<< "$step"
  export JAX_COMPILATION_CACHE_DIR=$repo/.jax_cache_call${cache:+_$cache}; mkdir -p $JAX_COMPILATION_CACHE_DIR
  now=$(( $(date +%s) - start ))
  need=${longest[$cell]}
  if [ $(( now + need )) -gt $budget ]; then echo "[skipped $step at $now s: $need s do not fit $budget s]"; continue; fi
  n=$(( n + 1 )); t0=$(date +%s)
  root=$repo; [ $tree != change ] && root=$repo/_scratch/$tree
  label=$n.$tree${form:+-$form}.$seed.t$trace
  rm -rf $root/.bench_work/$cell
  ( cd $root && ROTARY_FORM=$form python3 benchmark/run.py --workload $cell --seed $seed --seconds 40 --trace $trace ) > $out/$cell.$label.out 2> $out/$cell.$label.err; rc=$?
  took=$(( $(date +%s) - t0 ))
  work=$root/.bench_work/$cell
  grep -h '"event": "compile"' $work/telemetry.jsonl > $out/$cell.$label.compile.jsonl 2>/dev/null
  tail -n 1 $out/$cell.$label.out | sed "s/^{/{\"tree\": \"$tree${form:+-$form}\", \"seed\": $seed, \"trace\": $trace, \"order\": $n, \"rc\": $rc, \"took_s\": $took, /" >> $out/$cell.jsonl
  echo "[$n $tree $cell seed $seed trace $trace rc=$rc took $took s, cache $(du -sm $JAX_COMPILATION_CACHE_DIR | cut -f1) MB] $(tail -n 1 $out/$cell.$label.out | cut -c1-2600)"
  grep -h "^check\|^memory" $out/$cell.$label.out | head -9 | cut -c1-160
  python3 - $out/$cell.$label.compile.jsonl $work/telemetry.jsonl <<'P'
import sys, json, statistics
events = [json.loads(l) for l in open(sys.argv[1])]
print("  compile:", [{k: e.get(k) for k in ("fn", "lower_s", "compile_s", "scopes_s", "scopes")} for e in events])
rows = [json.loads(l) for l in open(sys.argv[2])]
rows = [e["execute_s"] for e in rows if e.get("event") == "epoch"]
if rows: print("  execute_s an epoch: first", round(rows[0], 4), "median", round(statistics.median(rows), 4), "max", round(max(rows), 4), "of", len(rows))
P
  if [ "$trace" = 1 ] && [ $rc -eq 0 ]; then
    JAX_PLATFORMS=cpu python3 $repo/bench_results/hw_pr34/all_ops.py $root $cell $out/$cell.$label.ops.json
    JAX_PLATFORMS=cpu python3 $repo/bench_results/hw_pr41/instructions.py $root $cell $out/$cell.$label.instructions.json
    grep -h "^device time by scope" $out/$cell.$label.out | cut -c1-6000
    [ -f $work/scope_time.json ] && cp $work/scope_time.json $out/$cell.$label.scope_time.json
    [ -f $work/telemetry.jsonl.scopes.json ] && gzip -c $work/telemetry.jsonl.scopes.json > $out/$cell.$label.scopes.json.gz
    if [ "${KEEP_TRACE:-0}" = 1 ]; then find $work/trace -name "*.xplane.pb" -exec sh -c 'gzip -c "$0" > '$out/$cell.$label.xplane.pb.gz {} \; ; fi
  fi
  if [ $rc -ne 0 ]; then tail -n 30 $out/$cell.$label.err; echo "[stopped: $step failed]"; exit 1; fi
done
echo "[call $call: $n runs in $(( $(date +%s) - start )) s]"
