"""The tree's own kda_fwd / kda_bwd (PR 38: the far pairs a doubling at a time, what no
state enters for all four chunks first, the inverses' products interleaved) at sub-blocks of
2, 4, 8 and 16: the checks and the timer of kernels_on_chip.py, nothing patched.
usage (chip only): python3 bench_results/hw_pr38/final_on_chip.py [out.jsonl]"""
import os, sys
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import kernels_on_chip as s0
from csed_514_project_distributed_training_using_pytorch_tpu.ops import kda

if __name__ == "__main__":
    say, (operands, shape) = s0.recorder(sys.argv), s0.sizes()
    for sub in (kda.KDA_TILING.sub, 2, 4, 8, 16, kda.KDA_TILING.sub):
        s0.measure(f"the tree (SUB = {kda.KDA_TILING.sub})", sub, say, operands, shape)
