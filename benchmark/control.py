"""python benchmark/control.py --workload <name> --seconds <s> [--seeds a,b,c] [--sound-seeds d,e,f]

Readings for the limits of ``correct``, several seeds in one process. Each of
``--seeds`` is one run of the cell with the *control* in the program's place:
for a trained configuration the plain reference at ``train.control_precision``.
Each of ``--sound-seeds`` is a sound run, so both readings come from one
set-up. The benchmark's own runs never come here.
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
    p.add_argument("--seeds", default="")
    p.add_argument("--sound-seeds", default="")
    p.add_argument("--seconds", type=float, required=True)
    args = p.parse_args(argv)
    sys.path.insert(0, ROOT)
    sys.path.insert(0, HERE)
    import harness
    runs = [(int(s), False) for s in args.sound_seeds.split(",") if s] + \
        [(int(s), True) for s in args.seeds.split(",") if s]
    for seed, control in runs:
        print(f"=== seed {seed} {'control' if control else 'sound'}", flush=True)
        harness.run_cell(ROOT, args.workload, seed=seed, seconds=args.seconds,
                         trace=False, t_process=time.perf_counter(), control=control)
    return 0


if __name__ == "__main__":
    sys.exit(main())
