#!/bin/bash
# Call C (one chip): what call B's time did not reach: an untraced pair of qwen3_next_train_8k and
# of lfm2_moe_train_8k, parent (_scratch/parent) and the final tree, one seed a cell, then the change traced in both.
cd "$(dirname "$(readlink -f "$0")")/../.." || exit 1
exec bash bench_results/hw_pr48/pairs.sh c ${BUDGET:-1500} \
  parent:qwen3_next_train_8k:4800000301:0 change:qwen3_next_train_8k:4800000301:0 \
  change:lfm2_moe_train_8k:4800000311:0 parent:lfm2_moe_train_8k:4800000311:0 \
  change:qwen3_next_train_8k:4800000302:1 change:lfm2_moe_train_8k:4800000312:1
