#!/bin/bash
# Call D (one chip): the committed files alone (`git archive $(git write-tree)` unpacked at
# _scratch/final): kanana2_train_8k on two fresh seeds, one of them traced.
exec bash bench_results/hw_pr40/pairs.sh d ${BUDGET:-1500} \
  final:kanana2_train_8k:4000000104:0 final:kanana2_train_8k:4000000105:1
