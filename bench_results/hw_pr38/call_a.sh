#!/bin/bash
# Call A (one chip): the tree's kernels alone at sub-blocks of 2, 4, 8, 16 (final_on_chip.py),
# then kimi_linear_train_8k parent, change, change, parent on two seeds, then one traced run a tree.
python3 bench_results/hw_pr38/final_on_chip.py chiprun_out/hw_pr38/final.jsonl || exit 1
exec bash bench_results/hw_pr38/pairs.sh a ${BUDGET:-3000} \
  parent:kimi_linear_train_8k:3800000101:0 change:kimi_linear_train_8k:3800000101:0 \
  change:kimi_linear_train_8k:3800000102:0 parent:kimi_linear_train_8k:3800000102:0 \
  parent:kimi_linear_train_8k:3800000103:1 change:kimi_linear_train_8k:3800000103:1
