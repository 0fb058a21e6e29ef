#!/bin/bash
# Parent and change in one call, on one chip, sharing one compile cache: each step runs one
# cell once from one tree. A step that does not fit the call's budget is skipped; a run that
# fails stops the call. `change` is the repo as it stands on disk; any other tree is a
# `git archive` unpacked at _scratch/<tree> (parent: the parent commit; final: `git write-tree`).
# usage: pairs.sh <call label> <budget seconds> <step> ...; a step is tree:cell:seed:trace,
#        or control:cell:seed for the fp8 control (benchmark/control.py of the repo on disk)
call=$1; budget=$2; shift 2
repo=/root/repo
out=$repo/chiprun_out/pr34/$call; mkdir -p $out
export JAX_COMPILATION_CACHE_DIR=$repo/.jax_cache_call JAX_COMPILATION_CACHE_MAX_SIZE=-1
mkdir -p $JAX_COMPILATION_CACHE_DIR
start=$(date +%s); n=0
declare -A longest=([kimi_linear_train_8k]=${KIMI_S:-600} [nemotron_h_train_8k]=450 [lfm2_moe_train_8k]=360 [lm_train_b16]=200)
for step in "$@"; do
  IFS=: read tree cell seed trace <<< "$step"
  now=$(( $(date +%s) - start ))
  if [ $tree = control ]; then need=520; else need=${longest[$cell]}; fi
  if [ $(( now + need )) -gt $budget ]; then echo "[skipped $step at $now s: $need s do not fit $budget s]"; continue; fi
  n=$(( n + 1 )); t0=$(date +%s)
  if [ $tree = control ]; then
    label=$n.control.$seed
    ( cd $repo && python3 benchmark/control.py --workload $cell --seconds 40 --seeds $seed ) > $out/$cell.$label.out 2> $out/$cell.$label.err; rc=$?
    echo "[control $cell $seed rc=$rc took $(( $(date +%s) - t0 )) s]"; grep -h "^check\|^reference\|correct" $out/$cell.$label.out | head -12
    continue
  fi
  root=$repo; [ $tree != change ] && root=$repo/_scratch/$tree
  label=$n.$tree.$seed.t$trace
  ( cd $root && python3 benchmark/run.py --workload $cell --seed $seed --seconds 40 --trace $trace ) > $out/$cell.$label.out 2> $out/$cell.$label.err; rc=$?
  took=$(( $(date +%s) - t0 ))
  tele=$root/.bench_work/$cell/telemetry.jsonl
  grep -h '"event": "epoch"' $tele 2>/dev/null | python3 -c "
import sys, json
for l in sys.stdin:
    e = json.loads(l); print(json.dumps({k: e.get(k) for k in ('epoch', 'execute_s', 'eval_s', 'period_s', 'train_loss', 'val_loss')} | {'rows_last_step': [round(8 * x) for x in (e.get('expert_rows_mean') or [[]])[-1]], 'rows_max_last_step': (e.get('expert_rows_max') or [[]])[-1]}))" > $out/$cell.$label.epochs.jsonl
  grep -h '"event": "compile"' $tele > $out/$cell.$label.compile.jsonl 2>/dev/null
  tail -n 1 $out/$cell.$label.out | sed "s/^{/{\"tree\": \"$tree\", \"seed\": $seed, \"trace\": $trace, \"order\": $n, \"rc\": $rc, \"took_s\": $took, /" >> $out/$cell.jsonl
  echo "[$n $tree $cell seed $seed trace $trace rc=$rc took $took s, cache $(du -sm $JAX_COMPILATION_CACHE_DIR | cut -f1) MB] $(tail -n 1 $out/$cell.$label.out | cut -c1-1800)"
  grep -h "^check\|routing:\|^memory" $out/$cell.$label.out | head -12
  python3 - $out/$cell.$label.compile.jsonl $out/$cell.$label.epochs.jsonl <<'P'
import sys, json, statistics
events = [json.loads(l) for l in open(sys.argv[1])]
print("  compile:", [{k: e.get(k) for k in ("fn", "lower_s", "compile_s", "head_products")} | {"kept_bytes": (e.get("recompute") or {}).get("kept_bytes")} for e in events])
rows = [json.loads(l)["execute_s"] for l in open(sys.argv[2])]
if rows: print("  execute_s an epoch: first", round(rows[0], 4), "median", round(statistics.median(rows), 4), "max", round(max(rows), 4), "of", len(rows))
P
  [ "$trace" = 1 ] && [ $rc -eq 0 ] && JAX_PLATFORMS=cpu python3 $repo/bench_results/hw_pr34/all_ops.py $root $cell $out/$cell.$label.ops.json
  if [ $rc -ne 0 ]; then tail -n 30 $out/$cell.$label.err; echo "[stopped: $step failed]"; exit 1; fi
done
echo "[call $call: $n runs in $(( $(date +%s) - start )) s]"
