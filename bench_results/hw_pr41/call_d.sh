#!/bin/bash
# Call D (one chip): the change (every norm's output of the float32 stream behind a barrier) as
# the committed files alone (`final`: `git archive $(git write-tree)` unpacked at _scratch/final)
# against the parent 08dadd7 in evabyte_train_32k: traced on call A's traced seed, then two
# untraced pairs on fresh seeds, parent first in one and the change first in the other.
exec bash bench_results/hw_pr41/pairs.sh d ${BUDGET:-2600} \
  final:evabyte_train_32k:4100000101:1 parent:evabyte_train_32k:4100000401:0 \
  final:evabyte_train_32k:4100000401:0 final:evabyte_train_32k:4100000402:0 \
  parent:evabyte_train_32k:4100000402:0
