"""python benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

One process, one cell, one run. Exits non-zero with one line of reason and no
result line when the devices are not the TPU chips the cell asks for.
"""

from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse     # noqa: E402
import os           # noqa: E402
import sys          # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = p.parse_args(argv)
    sys.path.insert(0, ROOT)            # the program's package
    sys.path.insert(0, HERE)            # the benchmark's own modules
    import harness
    try:
        harness.run_cell(ROOT, args.workload, seed=args.seed, seconds=args.seconds,
                         trace=bool(args.trace), t_process=T_PROCESS)
    except harness.Refused as e:
        print(f"benchmark refused: {e}", file=sys.stderr)
        return 2
    return 0


if __name__ == "__main__":
    sys.exit(main())
