#!/bin/bash
# Call C (one chip, SLOT=1 as call B): the three cells left, the order of the trees alternating.
SLOT=1 exec bash bench_results/hw_pr42/pairs.sh c ${BUDGET:-3400} \
  final:kanana2_train_8k:4200000301:a parent:kanana2_train_8k:4200000301:a \
  parent:nemotron_h_train_8k:4200000401:a final:nemotron_h_train_8k:4200000401:a \
  final:kimi_linear_train_8k:4200000501:a parent:kimi_linear_train_8k:4200000501:a
