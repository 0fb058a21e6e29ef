#!/bin/bash
# Call D: where the 58 ms a step under transpose(jvp(embed)) go: the embedding's backward alone,
# as the step takes it and as other formulations give it; then one traced run of the cell with
# its device time by instruction and by scope brought back.
cd "$(dirname "$(readlink -f "$0")")/../.." || exit 1   # the checkout this script lies in
out=$PWD/chiprun_out/hw_pr47; mkdir -p $out
export JAX_COMPILATION_CACHE_DIR=${JAX_COMPILATION_CACHE_DIR:-$PWD/.jax_cache_call_d} JAX_COMPILATION_CACHE_MAX_SIZE=-1
python3 bench_results/hw_pr47/embed_on_chip.py $out/embed_on_chip.jsonl 2> $out/embed_on_chip.err | cut -c1-300
s=$(date +%s)
python3 benchmark/run.py --workload falcon_h1_train_8k --seed ${SEED:-4700000401} --seconds 40 --trace 1 > $out/d_traced.out 2> $out/d_traced.err
echo "d_traced rc=$? wall=$(( $(date +%s) - s ))s"; grep "^device time by scope" $out/d_traced.out | cut -c1-1600; tail -n 1 $out/d_traced.out | cut -c1-1500
work=.bench_work/falcon_h1_train_8k
cp $work/scope_time.json $out/d_scope_time.json; cp $work/telemetry.jsonl.scopes.json $out/d_scopes_table.json
python3 bench_results/hw_pr41/instructions.py . falcon_h1_train_8k $out/d_instructions.json 16
python3 - <<'P'
import json
d = json.load(open("chiprun_out/hw_pr47/d_instructions.json"))
t = json.load(open("chiprun_out/hw_pr47/d_scopes_table.json"))["ops"]
for program, name, ms in d["ms_per_step"][:45]:
    print(f"{ms:9.3f} ms  {program:14s} {name:40s} {t.get(name, ['?', '?'])}")
P
