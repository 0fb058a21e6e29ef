#!/bin/bash
# Call B (one chip): the control's readings. The plain reference at fp8 in the program's
# place, two seeds, against the reference at highest: what the cell's limits have to refuse.
set -u
OUT=chiprun_out/hw_pr37; mkdir -p $OUT
export JAX_COMPILATION_CACHE_DIR=$PWD/.jax_cache_call_b JAX_COMPILATION_CACHE_MAX_SIZE=-1
t0=$(date +%s)
python3 benchmark/control.py --workload evabyte_train_32k --seeds 3700000201,3700000202 --seconds 40 > $OUT/b_control.out 2> $OUT/b_control.err
echo "control: rc $? after $(( $(date +%s) - t0 )) s"
grep -E "^===|^check |^reference:|^\{" $OUT/b_control.out | cut -c1-400
tail -3 $OUT/b_control.err | cut -c1-600
