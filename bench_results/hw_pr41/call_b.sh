#!/bin/bash
# Call B (one chip): evabyte_train_32k with the mixer's output kept by remat (`mixer_delta`, the
# change) against the parent 08dadd7: the change traced on call A's traced seed, two untraced
# pairs, and once a tree that also keeps attn_proj in two layers (_scratch/more, a reading for
# ROADMAP, not this PR's change).
exec bash bench_results/hw_pr41/pairs.sh b ${BUDGET:-2600} \
  change:evabyte_train_32k:4100000101:1 parent:evabyte_train_32k:4100000201:0 \
  change:evabyte_train_32k:4100000201:0 more:evabyte_train_32k:4100000201:0 \
  change:evabyte_train_32k:4100000202:0 parent:evabyte_train_32k:4100000202:0
