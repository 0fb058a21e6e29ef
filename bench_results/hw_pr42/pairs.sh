#!/bin/bash
# Parent and change in one call, on one chip: each step runs one cell once from one tree
# (bench_results/hw_pr41/pairs.sh, writing under pr42; what it adds: after every run the names of
# the compile cache's entries and the sha256 of the run's <telemetry>.scopes.json, which is how
# "the same program" is read: a tree whose run adds no entry to a cache the other tree filled ran
# executables whose cache keys, the HLO without its metadata among them, are the other tree's).
# `parent` is `git archive ba71b1a` unpacked at _scratch/parent, `final` `git archive $(git
# write-tree)` at _scratch/final: the committed files alone.
# usage: pairs.sh <call label> <budget seconds> <step> ...; a step is tree:cell:seed:cache, cache
#        naming the compile cache the run shares (each starts empty). SLOT=1 copies the tree to
#        _scratch/slot before each run and runs it from there: a Pallas kernel's Mosaic module
#        carries the source path of its lines, which is part of the cache's key, so two trees at
#        two paths never share a program that holds a kernel (call A), and two at one path do.
call=$1; budget=$2; shift 2
repo=/root/repo
out=$repo/chiprun_out/pr42/$call; mkdir -p $out
export JAX_COMPILATION_CACHE_MAX_SIZE=-1
start=$(date +%s); n=0
declare -A longest=([kanana2_train_8k]=600 [kimi_linear_train_8k]=480 [nemotron_h_train_8k]=450 [lfm2_moe_train_8k]=360 [lm_train_b16]=200 [evabyte_train_32k]=330)
for step in "$@"; do
  IFS=: read tree cell seed cache <<< "$step"
  export JAX_COMPILATION_CACHE_DIR=$repo/.jax_cache_call_$cache; mkdir -p $JAX_COMPILATION_CACHE_DIR
  now=$(( $(date +%s) - start ))
  need=${longest[$cell]}
  if [ $(( now + need )) -gt $budget ]; then echo "[skipped $step at $now s: $need s do not fit $budget s]"; continue; fi
  n=$(( n + 1 )); t0=$(date +%s)
  root=$repo/_scratch/$tree
  if [ "${SLOT:-0}" = 1 ]; then root=$repo/_scratch/slot; rm -rf $root; cp -a $repo/_scratch/$tree $root; fi
  label=$n.$tree.$cache
  rm -rf $root/.bench_work/$cell
  ( cd $root && python3 benchmark/run.py --workload $cell --seed $seed --seconds 40 --trace 0 ) > $out/$cell.$label.out 2> $out/$cell.$label.err; rc=$?
  took=$(( $(date +%s) - t0 ))
  work=$root/.bench_work/$cell
  grep -h '"event": "compile"' $work/telemetry.jsonl > $out/$cell.$label.compile.jsonl 2>/dev/null
  ls $JAX_COMPILATION_CACHE_DIR | grep -v -- "-atime$" | sort > $out/$cell.$label.cache_entries.txt
  [ -n "$before" ] && [ "$before" != "$out/$cell.$label.cache_entries.txt" ] && [ "$lastcache" = "$cell.$cache" ] && echo "  entries this run added to cache $cache: $(comm -13 $before $out/$cell.$label.cache_entries.txt | cut -c1-40 | tr '\n' ' ')"
  before=$out/$cell.$label.cache_entries.txt; lastcache=$cell.$cache
  table=$(sha256sum $work/telemetry.jsonl.scopes.json | cut -d' ' -f1)
  tail -n 1 $out/$cell.$label.out | sed "s/^{/{\"tree\": \"$tree\", \"seed\": $seed, \"cache\": \"$cache\", \"order\": $n, \"rc\": $rc, \"took_s\": $took, \"cache_entries\": $(wc -l < $out/$cell.$label.cache_entries.txt), \"scopes_json_sha256\": \"$table\", /" >> $out/$cell.jsonl
  echo "[$n $tree $cell seed $seed cache $cache rc=$rc took $took s, $(wc -l < $out/$cell.$label.cache_entries.txt) cache entries, scopes.json $table] $(tail -n 1 $out/$cell.$label.out | cut -c1-2400)"
  grep -h "^check\|^memory" $out/$cell.$label.out | head -9 | cut -c1-160
  if [ $rc -ne 0 ]; then tail -n 30 $out/$cell.$label.err; echo "[stopped: $step failed]"; exit 1; fi
done
echo "[call $call: $n runs in $(( $(date +%s) - start )) s]"
