#!/bin/bash
# Call E (one chip): two cells whose programs are the parent's hash for hash, one pair each, the
# committed files alone (`final`) against the parent 08dadd7: lfm2_moe_train_8k, kimi_linear_train_8k.
exec bash bench_results/hw_pr41/pairs.sh e ${BUDGET:-2000} \
  parent:lfm2_moe_train_8k:4100000501:0 final:lfm2_moe_train_8k:4100000501:0 \
  final:kimi_linear_train_8k:4100000601:0 parent:kimi_linear_train_8k:4100000601:0
