#!/bin/bash
# The refused cell again, with the selection bias's rule: untraced seeds, one traced run,
# then the fp8 control, each only if a cold run still fits the call's budget.
# usage: m30e.sh <budget seconds> <step> ...; a step is seed:trace:label or control:seed
budget=$1; shift
cell=nemotron_h_train_8k
out=/root/repo/chiprun_out/pr30/e; mkdir -p $out
export JAX_COMPILATION_CACHE_DIR=/root/repo/.jax_cache_call JAX_COMPILATION_CACHE_MAX_SIZE=-1
mkdir -p $JAX_COMPILATION_CACHE_DIR
start=$(date +%s); longest=430
for step in "$@"; do
  now=$(( $(date +%s) - start ))
  if [ $(( now + longest )) -gt $budget ]; then echo "[skipped $step at $now s: a run of $longest s does not fit $budget s]"; continue; fi
  IFS=: read seed trace label <<< "$step"
  t0=$(date +%s)
  if [ $seed = control ]; then
    python3 benchmark/control.py --workload $cell --seconds 40 --seeds $trace > $out/$cell.control$trace.out 2> $out/$cell.control$trace.err; rc=$?
    echo "[control $trace rc=$rc took $(( $(date +%s) - t0 )) s]"; grep -h "^check\|^reference" $out/$cell.control$trace.out | head -12
    [ $rc -ne 0 ] && tail -n 15 $out/$cell.control$trace.err
    continue
  fi
  python3 benchmark/run.py --workload $cell --seed $seed --seconds 40 --trace $trace > $out/$cell.$label.out 2> $out/$cell.$label.err; rc=$?
  took=$(( $(date +%s) - t0 )); [ $label != r1 ] && [ $took -lt $longest ] && longest=$(( took + 30 ))
  grep -h '"event": "epoch"' .bench_work/$cell/telemetry.jsonl 2>/dev/null | python3 -c "
import sys, json
for l in sys.stdin:
    e=json.loads(l); print(json.dumps({k:e.get(k) for k in ('epoch','execute_s','eval_s','period_s','train_loss','expert_rows_max','expert_rows_mean','expert_rows_moved')}))" > $out/$cell.$label.epochs.jsonl
  grep -h '"event": "compile"' .bench_work/$cell/telemetry.jsonl > $out/$cell.$label.compile.jsonl 2>/dev/null
  tail -n 1 $out/$cell.$label.out | sed "s/^{/{\"set\": \"trace$trace\", \"label\": \"$label\", \"seed\": $seed, \"rc\": $rc, \"took_s\": $took, /" >> $out/$cell.jsonl
  echo "[$label seed $seed trace $trace rc=$rc took $took s, cache $(du -sm $JAX_COMPILATION_CACHE_DIR | cut -f1) MB] $(tail -n 1 $out/$cell.$label.out | cut -c1-1500)"
  grep -h "^check\|routing:\|^memory\|^train:" $out/$cell.$label.out | head -14
  python3 - $out/$cell.$label.epochs.jsonl <<'P'
import sys, json
rows = [json.loads(l) for l in open(sys.argv[1])]
for e in rows[:1] + rows[1::4] + rows[-1:]:
    print("  epoch", e["epoch"], "execute_s", round(e["execute_s"], 4), "rows a layer (last step)", [round(8 * x) for x in e["expert_rows_mean"][-1]], "max", e["expert_rows_max"][-1])
P
  [ $rc -ne 0 ] && tail -n 25 $out/$cell.$label.err
done
python3 benchmark/spread.py $out/$cell.jsonl 2>&1 | tail -5
