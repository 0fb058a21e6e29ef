#!/bin/bash
# Call A (one chip): the flash backward alone, fused against split, at the cells' shapes and at
# blocks of 1024 and 512 (kernels_on_chip.py); then kanana2_train_8k parent, change, change,
# parent on two seeds and one traced run a tree.
mkdir -p chiprun_out/hw_pr40
python3 bench_results/hw_pr40/kernels_on_chip.py chiprun_out/hw_pr40/kernels.jsonl 2> chiprun_out/hw_pr40/kernels.err || { tail -20 chiprun_out/hw_pr40/kernels.err; echo "[kernels_on_chip failed]"; }
exec bash bench_results/hw_pr40/pairs.sh a ${BUDGET:-3250} \
  parent:kanana2_train_8k:4000000101:0 change:kanana2_train_8k:4000000101:0 \
  parent:kanana2_train_8k:4000000103:1 change:kanana2_train_8k:4000000103:1 \
  change:kanana2_train_8k:4000000102:0 parent:kanana2_train_8k:4000000102:0
