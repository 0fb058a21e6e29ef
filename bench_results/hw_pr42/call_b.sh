#!/bin/bash
# Call B (one chip, SLOT=1: both trees run from one path, _scratch/slot): per cell one tree cold and
# then the other on the cache the first filled. A second run that adds no `jit_epoch` /
# `jit_evaluate` entry ran the first tree's executables: the same programs, kernels' bodies and all.
SLOT=1 exec bash bench_results/hw_pr42/pairs.sh b ${BUDGET:-3300} \
  parent:lm_train_b16:4200000103:a final:lm_train_b16:4200000103:a \
  final:lfm2_moe_train_8k:4200000203:a parent:lfm2_moe_train_8k:4200000203:a \
  parent:evabyte_train_32k:4200000601:a final:evabyte_train_32k:4200000601:a
