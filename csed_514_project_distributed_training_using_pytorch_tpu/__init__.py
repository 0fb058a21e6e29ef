"""TPU-native distributed-training framework.

A brand-new JAX/XLA re-design of the capabilities of the reference project
``abhishekiitm/CSED_514_Project_Distributed_Training_using_PyTorch`` (CPU PyTorch DDP over the
gloo TCP backend): an MNIST CNN trained single-process and data-parallel across devices/hosts,
with loss-curve and time-to-train-vs-worker-count benchmarking.

Instead of a DDP wrapper object, per-rank launcher scripts, and a backend string
(reference ``src/train_dist.py:63,146``, ``src/run1.py``/``src/run2.py``), this framework is
SPMD-first: one jit-compiled train step over a ``jax.sharding.Mesh``, with the gradient
all-reduce fused into the compiled program by XLA and laid onto ICI/DCN by the compiler.

Layout (mirrors the reference's five functional layers, SURVEY.md §1):

- ``ops/``       functional NN ops on ``jax.numpy``/``lax`` (the ATen-kernel analog)
- ``models/``    model definitions (reference ``src/model.py``)
- ``data/``      MNIST ingest + host input pipeline (reference data loaders), incl. a native
                 C++ batch-assembly path (the DataLoader-worker-pool analog)
- ``parallel/``  mesh construction, SPMD data-parallel train step, sharded sampler,
                 collectives (the C10D/gloo + DDP-Reducer analog)
- ``train/``     training drivers: single-process, distributed, p2p smoke test
                 (reference ``src/train.py``, ``src/train_dist.py``, ``src/run{1,2}.py``)
- ``utils/``     config, checkpointing (save *and* the restore path the reference lacks),
                 metrics/plots, profiling, determinism checks
"""

# Lazy exports (PEP 562): importing ANY submodule executes this __init__, and
# the backend-free fleet side (serving/router.py, resilience/supervisor.py,
# utils/jsonl.py — see tools/graftlint's backend-purity checker) lives inside
# this package. An eager `from .models.cnn import Net` here charged every one
# of them for jax+flax at import time; the attribute shim keeps the public
# `package.Net` / `package.SingleProcessConfig` surface identical while
# deferring the heavyweight import to first touch.
_LAZY_EXPORTS = {
    "Net": ("csed_514_project_distributed_training_using_pytorch_tpu"
            ".models.cnn"),
    "SingleProcessConfig": ("csed_514_project_distributed_training_using"
                            "_pytorch_tpu.utils.config"),
    "DistributedConfig": ("csed_514_project_distributed_training_using"
                          "_pytorch_tpu.utils.config"),
}

__version__ = "0.1.0"

__all__ = [
    "Net",
    "SingleProcessConfig",
    "DistributedConfig",
    "__version__",
]


def __getattr__(name: str):
    if name in _LAZY_EXPORTS:
        import importlib

        value = getattr(importlib.import_module(_LAZY_EXPORTS[name]), name)
        globals()[name] = value      # cache: subsequent lookups skip __getattr__
        return value
    raise AttributeError(
        f"module {__name__!r} has no attribute {name!r}")


def __dir__():
    return sorted(set(globals()) | set(__all__))
