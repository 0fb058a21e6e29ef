#!/bin/bash
# Call B (one chip): the committed files alone (_scratch/final = git archive of `git write-tree`)
# against the parent (_scratch/parent = git archive of d9f1d90) on two more seeds, final, parent,
# parent, final; then the fp8 control of the cell from the final tree (benchmark/control.py:
# the plain reference at fp8 in the program's place, which the cell's limits must still refuse).
bash bench_results/hw_pr38/pairs.sh b ${BUDGET:-2400} \
  final:kimi_linear_train_8k:3800000201:0 parent:kimi_linear_train_8k:3800000201:0 \
  parent:kimi_linear_train_8k:3800000202:0 final:kimi_linear_train_8k:3800000202:0 || exit 1
out=chiprun_out/pr38/b; t0=$(date +%s)
( cd _scratch/final && python3 benchmark/control.py --workload kimi_linear_train_8k --seconds 40 --seeds 3800000203 ) > $out/control_fp8.out 2> $out/control_fp8.err
echo "control: rc $? after $(( $(date +%s) - t0 )) s"
grep -E "^===|^check |^reference:|^\{" $out/control_fp8.out | cut -c1-400
tail -3 $out/control_fp8.err | cut -c1-600
