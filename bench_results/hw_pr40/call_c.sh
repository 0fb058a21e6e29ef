#!/bin/bash
# Call C (one chip): lm_train_b16 and evabyte_train_32k, parent and change on one seed and one
# traced run a tree; then nemotron_h_train_8k, which the mechanism bypasses, one pair.
exec bash bench_results/hw_pr40/pairs.sh c ${BUDGET:-3400} \
  parent:lm_train_b16:4000000401:0 change:lm_train_b16:4000000401:0 \
  parent:lm_train_b16:4000000403:1 change:lm_train_b16:4000000403:1 \
  parent:evabyte_train_32k:4000000501:0 change:evabyte_train_32k:4000000501:0 \
  parent:evabyte_train_32k:4000000503:1 change:evabyte_train_32k:4000000503:1 \
  parent:nemotron_h_train_8k:4000000601:0 change:nemotron_h_train_8k:4000000601:0
