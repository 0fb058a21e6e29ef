# the final tree (`git archive $(git write-tree)` unpacked at _scratch/final): one untraced and one traced run of the
# new cell, then the parent, with the benchmark as this PR leaves it laid over it, on the new cell (it has to refuse at once)
bash /root/repo/bench_results/hw_pr32/pairs.sh e 1300 "$@"
( cd /root/repo/_scratch/parent && timeout 600 python3 benchmark/run.py --workload kimi_linear_train_8k --seed 3200000503 --seconds 40 --trace 0 > /root/repo/chiprun_out/pr32/e/parent_kimi.out 2> /root/repo/chiprun_out/pr32/e/parent_kimi.err; echo "parent on the new cell: rc=$? after $SECONDS s"; tail -n 4 /root/repo/chiprun_out/pr32/e/parent_kimi.err; tail -n 2 /root/repo/chiprun_out/pr32/e/parent_kimi.out )
