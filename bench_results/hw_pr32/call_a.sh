# the parent on the new cell (it has to refuse the file at once), then the change: one untraced and one traced run
( cd /root/repo/_scratch/parent && timeout 600 python3 benchmark/run.py --workload kimi_linear_train_8k --seed 3200000101 --seconds 40 --trace 0 > /root/repo/chiprun_out/pr32_parent_kimi.out 2> /root/repo/chiprun_out/pr32_parent_kimi.err; echo "parent on the new cell: rc=$? after $SECONDS s"; tail -n 4 /root/repo/chiprun_out/pr32_parent_kimi.err; tail -n 2 /root/repo/chiprun_out/pr32_parent_kimi.out )
bash /root/repo/bench_results/hw_pr32/pairs.sh a 1700 "$@"
