"""One run of the cell through benchmark/run.py with a fault planted in the program, in this
process: FAULT=mu leaves the five ssm_multipliers at one, FAULT=key leaves key_multiplier at
one. The benchmark, the reference and the limits are the committed ones.
usage (chip): FAULT=mu python3 bench_results/hw_pr47/run_faulty.py --workload falcon_h1_train_8k --seed N --seconds 40"""
import dataclasses, os, sys
ROOT = os.path.realpath(os.path.join(os.path.dirname(os.path.abspath(__file__)), "..", ".."))
sys.path[:0] = [ROOT, os.path.join(ROOT, "benchmark")]
from csed_514_project_distributed_training_using_pytorch_tpu.models import hybrid_lm
FAULTS = {"mu": dict(ssm=(1.0,) * 5), "key": dict(key=1.0)}
fault, parse = FAULTS[os.environ["FAULT"]], hybrid_lm._FAMILIES["falcon_h1"]


def faulty(config):
    pattern, fields = parse(config)
    return pattern, dict(fields, multipliers=dataclasses.replace(fields["multipliers"], **fault))


hybrid_lm._FAMILIES["falcon_h1"] = faulty
print(f"planted fault: {os.environ['FAULT']} -> {fault}", flush=True)
import run
sys.exit(run.main())
