"""Fixtures of the benchmark's own tests (CPU; `python -m pytest benchmark/tests`).

`tiny_root` is a temporary copy of the benchmark (manifest + directory) whose
configuration and traffic are cut to a size a test run can hold. The drivers
run from it through `harness.run_cell(..., require_chip=False)`: everything of
a run except the look for a chip.
"""

import json
import os
import shutil
import sys

import pytest

os.environ.setdefault("JAX_PLATFORMS", "cpu")

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REPO = os.path.dirname(BENCH)
for path in (REPO, BENCH):
    if path not in sys.path:
        sys.path.insert(0, path)


def _edit(path, fn):
    with open(path) as fh:
        obj = json.load(fh)
    fn(obj)
    with open(path, "w") as fh:
        json.dump(obj, fh)


def shrink(root):
    bench = os.path.join(root, "benchmark")

    def lm(c):
        small = dict(embed_dim=32, num_layers=2, num_heads=4, kv_heads=2)
        c["model"].update(small, head_dim=8)
        c["train"]["args"].update(small, bf16=False)
    _edit(os.path.join(bench, "configs", "pixel-lm-d1024.json"), lm)

    def train(t):
        t.update(batch=4, steps_per_epoch=5, test_examples=4)
        t["trainer_args"].update(batch_size=4, eval_batch=4)
    _edit(os.path.join(bench, "traffic", "train_b16.json"), train)


@pytest.fixture(scope="session")
def tiny_root(tmp_path_factory):
    root = str(tmp_path_factory.mktemp("bench_root"))
    shutil.copytree(BENCH, os.path.join(root, "benchmark"),
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), root)
    shrink(root)
    return root


@pytest.fixture()
def run_cell(tiny_root):
    import time

    import harness

    def run(workload, *, seed=3000000007, seconds=2.0, root=None, trace=False, **kw):
        lines = []
        result = harness.run_cell(root or tiny_root, workload, seed=seed,
                                  seconds=seconds, trace=trace,
                                  t_process=time.perf_counter(), require_chip=False,
                                  out=lines.append, **kw)
        return result, lines

    return run
