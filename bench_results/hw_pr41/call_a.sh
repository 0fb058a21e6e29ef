#!/bin/bash
# Call A (one chip): step 0 (step0.py: one norm alone, three ways), then evabyte_train_32k
# parent, change traced, and change, parent on a second seed.
mkdir -p chiprun_out/hw_pr41
python3 bench_results/hw_pr41/step0.py chiprun_out/hw_pr41/step0.jsonl 2> chiprun_out/hw_pr41/step0.err || { tail -20 chiprun_out/hw_pr41/step0.err; echo "[step0 failed]"; }
exec bash bench_results/hw_pr41/pairs.sh a ${BUDGET:-2600} \
  parent:evabyte_train_32k:4100000101:1 change:evabyte_train_32k:4100000101:1 \
  change:evabyte_train_32k:4100000102:0 parent:evabyte_train_32k:4100000102:0
