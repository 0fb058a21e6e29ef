#!/bin/bash
# Call G (one chip, second session): the fp8 control on the files as committed (the bias's
# rate 0.001, `loss_gap` 0.001), seed …207 as in call B; call F's time ended before it.
set -u
OUT=$PWD/chiprun_out/hw_pr39; mkdir -p $OUT
export JAX_COMPILATION_CACHE_DIR=${JAX_COMPILATION_CACHE_DIR:-$PWD/.jax_cache_call_g} JAX_COMPILATION_CACHE_MAX_SIZE=-1
t0=$(date +%s)
python3 benchmark/control.py --workload kanana2_train_8k --seeds 3900000207 --seconds 40 > $OUT/g_control.out 2> $OUT/g_control.err
echo "control: rc $? after $(( $(date +%s) - t0 )) s"
grep -E "^===|^check |^reference:|^\{" $OUT/g_control.out | cut -c1-400
tail -3 $OUT/g_control.err | cut -c1-600
exit 0
