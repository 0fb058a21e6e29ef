#!/bin/bash
# Call A (one chip; chips were scarce, so the sweep and the first pairs share a call): step 0, the
# scan alone by tiling, both branches (scan_on_chip.py); then qwen3_next_train_8k, parent ef2796f
# and the change in one call: the traced pair on one seed, then untraced pairs on fresh seeds,
# the side that runs first alternating, while the call's time lasts.
T0=$(date +%s)
mkdir -p chiprun_out/hw_pr44
TILES="64,4,4;64,4,8;64,4,16;128,8,2;128,8,4;128,4,4;128,16,4;256,8,1;128,8,8;64,4,16,48;128,8,4,48;128,8,8,48;128,4,4,32;64,4,4" \
  python3 bench_results/hw_pr44/scan_on_chip.py chiprun_out/hw_pr44/scan_tilings.jsonl > chiprun_out/hw_pr44/scan_on_chip.out 2> chiprun_out/hw_pr44/scan_on_chip.err \
  || { tail -20 chiprun_out/hw_pr44/scan_on_chip.err; echo "[the sweep failed]"; }
python3 - <<'P'
import json
for line in open("chiprun_out/hw_pr44/scan_tilings.jsonl"):
    r = json.loads(line)
    print(r["branch"], r["chunk"], r["sub"], r["group"], r.get("vmem_limit_mib"),
          "refused: " + r["refused"][-120:] if "refused" in r else
          [round(r[k], 2) for k in ("forward_ms", "backward_ms", "forward_backward_ms")]
          + [round(r["worst_gap_to_first"], 4), r["forward_backward_compile_s"]])
P
echo "[the sweep: $(( $(date +%s) - T0 )) s]"
exec bash bench_results/hw_pr44/pairs.sh a $(( ${BUDGET:-3350} - ($(date +%s) - T0) )) \
  parent:qwen3_next_train_8k:4400000101:1 change:qwen3_next_train_8k:4400000101:1 \
  change:qwen3_next_train_8k:4400000102:0 parent:qwen3_next_train_8k:4400000102:0 \
  parent:qwen3_next_train_8k:4400000103:0 change:qwen3_next_train_8k:4400000103:0 \
  change:qwen3_next_train_8k:4400000104:0 parent:qwen3_next_train_8k:4400000104:0
