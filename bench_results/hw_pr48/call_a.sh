#!/bin/bash
# Call A (one chip): step 0 (rotary_alone.py: the rotation alone, three forms, every cell's
# shapes), then evabyte_train_32k traced from the parent and from the change in four variants of
# the form (a scratch switch, ROTARY_FORM, of the tree at _scratch/exp: this PR before its form was chosen): the permutation form
# behind an optimization barrier, without it, the roll kernel, and the parent's slices behind the
# barrier with the new backward; then two untraced pairs of the first variant against the parent.
# _scratch/exp is not committed (the roll kernel of it is roll_kernel.py, its forms are named in
# PERF.md section 6): to run this again, point `exp` at a tree with such a switch, or use call_b.sh.
cd "$(dirname "$(readlink -f "$0")")/../.." || exit 1
mkdir -p chiprun_out/pr48
python3 _scratch/exp/bench_results/hw_pr48/rotary_alone.py chiprun_out/pr48/rotary_alone.jsonl > chiprun_out/pr48/rotary_alone.out 2> chiprun_out/pr48/rotary_alone.err || { tail -20 chiprun_out/pr48/rotary_alone.err; echo "[rotary_alone failed]"; }
python3 - <<'P'
import json
for l in open("chiprun_out/pr48/rotary_alone.out"):
    if l.startswith("{"):
        r = json.loads(l)
        print(r["tensor"], r["form"], r.get("value_ms"), r.get("value_and_vjp_ms"), r["read_and_write_ms_at_819GBs"],
              r.get("max_abs_difference_from_present"), r.get("error", r.get("skipped", ""))[:200])
P
S=4800000101
exec bash bench_results/hw_pr48/pairs.sh a ${BUDGET:-2300} \
  parent:evabyte_train_32k:$S:1 exp:evabyte_train_32k:$S:1:barrier \
  exp:evabyte_train_32k:$S:1:roll exp:evabyte_train_32k:$S:1:permutation \
  exp:evabyte_train_32k:$S:1:bslices \
  exp:evabyte_train_32k:4800000102:0:barrier parent:evabyte_train_32k:4800000102:0 \
  parent:evabyte_train_32k:4800000103:0 exp:evabyte_train_32k:4800000103:0:barrier
