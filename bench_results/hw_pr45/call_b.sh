#!/bin/bash
# Call B (one chip; NEVER RAN: twenty-six askings from 23:13 to 00:49 UTC, no machine): what call A's hour did not hold. qwen3_next_train_8k, parent e25159f and the
# change in one call, untraced pairs on fresh seeds, the side that runs first alternating (the
# traced pair ran in call A); then further untraced pairs of kimi_linear_train_8k on seeds of
# their own, while the call's time lasts. The session that wrote this ends at 01:58 UTC, so the
# call stops starting runs that would end after 01:20 whatever time it gets its machine.
left=$(( $(date -u -d "2026-10-04 01:20:00" +%s) - $(date -u +%s) ))
budget=${BUDGET:-3450}; [ $left -lt $budget ] && budget=$left
exec bash bench_results/hw_pr45/pairs.sh b $budget \
  change:qwen3_next_train_8k:4500000202:0 parent:qwen3_next_train_8k:4500000202:0 \
  parent:qwen3_next_train_8k:4500000203:0 change:qwen3_next_train_8k:4500000203:0 \
  change:qwen3_next_train_8k:4500000204:0 parent:qwen3_next_train_8k:4500000204:0 \
  parent:kimi_linear_train_8k:4500000105:0 change:kimi_linear_train_8k:4500000105:0 \
  change:kimi_linear_train_8k:4500000106:0 parent:kimi_linear_train_8k:4500000106:0
