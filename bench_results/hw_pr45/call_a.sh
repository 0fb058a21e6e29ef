#!/bin/bash
# Call A (one chip): step 0, the scan alone by tiling (bench_results/hw_pr44/scan_on_chip.py; a
# tiling without a fourth number runs at ops/kda.py's own VMEM_LIMIT, 32 MiB): per channel the
# parent's (64, 4, 4), the committed (128, 4, 4), the two never timed, (128, 4, 8) (30.68 MiB of
# scoped fast memory by the compile for a described v5e) and rows of 2048, (128, 4, 16), which
# needs 59.25 MiB and so a limit of 64; scalar the parent's (128, 8, 4) and the committed
# (128, 8, 8). Then kimi_linear_train_8k, parent e25159f and the change in one call: the traced
# pair on one seed, then untraced pairs on fresh seeds, the side that runs first alternating; then
# the same of qwen3_next_train_8k as far as the call's time lasts (chips were scarce: no machine in
# the first two hours and thirty askings of this PR; what does not fit is call B's).
T0=$(date +%s)
mkdir -p chiprun_out/hw_pr45
BRANCHES=kda TILES="64,4,4;128,4,4;128,4,8;128,4,16,64;64,4,4" \
  python3 bench_results/hw_pr44/scan_on_chip.py chiprun_out/hw_pr45/scan_tilings.jsonl > chiprun_out/hw_pr45/scan_on_chip.out 2> chiprun_out/hw_pr45/scan_on_chip.err \
  || { tail -20 chiprun_out/hw_pr45/scan_on_chip.err; echo "[the per-channel sweep failed]"; }
BRANCHES=gdn TILES="128,8,4;128,8,8;128,8,4" \
  python3 bench_results/hw_pr44/scan_on_chip.py chiprun_out/hw_pr45/scan_tilings.jsonl >> chiprun_out/hw_pr45/scan_on_chip.out 2>> chiprun_out/hw_pr45/scan_on_chip.err \
  || { tail -20 chiprun_out/hw_pr45/scan_on_chip.err; echo "[the scalar sweep failed]"; }
python3 - <<'P'
import json
for line in open("chiprun_out/hw_pr45/scan_tilings.jsonl"):
    r = json.loads(line)
    print(r["branch"], r["chunk"], r["sub"], r["group"], r.get("vmem_limit_mib"),
          "refused: " + r["refused"][-120:] if "refused" in r else
          [round(r[k], 2) for k in ("forward_ms", "backward_ms", "forward_backward_ms")]
          + [round(r["worst_gap_to_first"], 4), r["forward_compile_s"], r["forward_backward_compile_s"]])
P
echo "[the sweep: $(( $(date +%s) - T0 )) s]"
exec bash bench_results/hw_pr45/pairs.sh a $(( ${BUDGET:-3350} - ($(date +%s) - T0) )) \
  parent:kimi_linear_train_8k:4500000101:1 change:kimi_linear_train_8k:4500000101:1 \
  change:kimi_linear_train_8k:4500000102:0 parent:kimi_linear_train_8k:4500000102:0 \
  parent:kimi_linear_train_8k:4500000103:0 change:kimi_linear_train_8k:4500000103:0 \
  change:kimi_linear_train_8k:4500000104:0 parent:kimi_linear_train_8k:4500000104:0 \
  parent:qwen3_next_train_8k:4500000201:1 change:qwen3_next_train_8k:4500000201:1 \
  change:qwen3_next_train_8k:4500000202:0 parent:qwen3_next_train_8k:4500000202:0
