#!/bin/bash
# Call C (one chip): four ways of taking the feed-forward norm's backward out of the epilogue of
# the product before it (fusion.2053 of the parent: 14.4 ms for a 7.5 ms product), each a tree
# under _scratch/v_* with one mode wired into HybridLM.normed, untraced, one seed:
# an optimization barrier on the norm's output (both-ff_, both-all), on its cotangent alone
# (bwd-ff_), or stream_norm_bwd for the backward alone (kernel-ff_).
exec bash bench_results/hw_pr41/pairs.sh c ${BUDGET:-2600} \
  v_bwd-ff_:evabyte_train_32k:4100000301:0 v_both-ff_:evabyte_train_32k:4100000301:0 \
  v_kernel-ff_:evabyte_train_32k:4100000301:0 v_both-all:evabyte_train_32k:4100000301:0 \
  parent:evabyte_train_32k:4100000301:0
