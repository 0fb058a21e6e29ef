# the kernels alone (the recurrence, the same bits twice, times), then the final tree (`git archive $(git write-tree)`
# unpacked at _scratch/final): an untraced run on a new seed, one on call B's first seed (the same tree and seed: the
# same losses), a traced run, and the parent traced on the same seed, both traces reduced to every op
python3 /root/repo/bench_results/hw_pr34/kernels_on_chip.py 2>/dev/null; echo "[kernels rc=$? at $SECONDS s]"
bash /root/repo/bench_results/hw_pr34/pairs.sh f 2300 "$@"
