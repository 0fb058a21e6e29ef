#!/bin/bash
# Call B (one chip): kimi_linear_train_8k and lfm2_moe_train_8k, parent and change on one seed,
# then one traced run a tree.
exec bash bench_results/hw_pr40/pairs.sh b ${BUDGET:-3400} \
  parent:kimi_linear_train_8k:4000000201:0 change:kimi_linear_train_8k:4000000201:0 \
  parent:kimi_linear_train_8k:4000000203:1 change:kimi_linear_train_8k:4000000203:1 \
  parent:lfm2_moe_train_8k:4000000301:0 change:lfm2_moe_train_8k:4000000301:0 \
  parent:lfm2_moe_train_8k:4000000303:1 change:lfm2_moe_train_8k:4000000303:1
