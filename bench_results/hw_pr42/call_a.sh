#!/bin/bash
# Call A (one chip): the two cells the issue names, parent ba71b1a against the committed files
# (`final`), each order once. Cache `a` is filled by the parent and then read by the change, cache
# `b` the other way round: the first run of each cache is cold, the second says whether the other
# tree's program is this tree's (no new entry).
exec bash bench_results/hw_pr42/pairs.sh a ${BUDGET:-3300} \
  parent:lm_train_b16:4200000101:a final:lm_train_b16:4200000101:a \
  final:lm_train_b16:4200000102:b parent:lm_train_b16:4200000102:b \
  parent:lfm2_moe_train_8k:4200000201:a final:lfm2_moe_train_8k:4200000201:a \
  final:lfm2_moe_train_8k:4200000202:b parent:lfm2_moe_train_8k:4200000202:b
