#!/bin/bash
# Call B (one chip): the final tree. First the claimed cell (the change traced, then an untraced
# pair), then the cells that share the changed code, those most at risk first: an untraced pair
# (parent, change) on one seed and the change traced, in falcon_h1_train_8k, kanana2_train_8k,
# lm_train_b16, qwen3_next_train_8k, lfm2_moe_train_8k, as far as the call's time reaches (pairs.sh
# skips what no longer fits). The change is the tree as it stands; the parent is _scratch/parent.
cd "$(dirname "$(readlink -f "$0")")/../.." || exit 1
exec bash bench_results/hw_pr48/pairs.sh b ${BUDGET:-3350} \
  change:evabyte_train_32k:4800000321:1 \
  parent:evabyte_train_32k:4800000322:0 change:evabyte_train_32k:4800000322:0 \
  parent:falcon_h1_train_8k:4800000201:0 change:falcon_h1_train_8k:4800000201:0 change:falcon_h1_train_8k:4800000202:1 \
  parent:kanana2_train_8k:4800000211:0 change:kanana2_train_8k:4800000211:0 change:kanana2_train_8k:4800000212:1 \
  parent:lm_train_b16:4800000221:0 change:lm_train_b16:4800000221:0 change:lm_train_b16:4800000222:1 \
  parent:qwen3_next_train_8k:4800000301:0 change:qwen3_next_train_8k:4800000301:0 change:qwen3_next_train_8k:4800000302:1 \
  parent:lfm2_moe_train_8k:4800000311:0 change:lfm2_moe_train_8k:4800000311:0 change:lfm2_moe_train_8k:4800000312:1
