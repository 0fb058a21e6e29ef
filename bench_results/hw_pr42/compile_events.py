"""The `compile` event, the `epoch` events' losses and expert rows and the scope table of a
`train.lm` run of each family's tiny preset (tests/test_<family>.py::tiny_config, as the
module-scoped `trained` fixtures run it: --remat, one device, the fixture corpus) and of a tiny
pixel LM, from the tree given: one JSON line a run with the timing fields and paths dropped, so
that two trees' outputs compare with `diff` (CPU: what is compared is what the trainer assembles
and what the program computes, no device time).
usage: JAX_PLATFORMS=cpu python compile_events.py <repo root to import from> <out.jsonl>"""
import hashlib, importlib, json, os, sys, tempfile
os.environ.setdefault("JAX_PLATFORMS", "cpu")
root, out_path = os.path.realpath(sys.argv[1]), os.path.realpath(sys.argv[2])
sys.path[:0] = [root, os.path.join(root, "tests")]
from csed_514_project_distributed_training_using_pytorch_tpu.models import hybrid_lm
from csed_514_project_distributed_training_using_pytorch_tpu.train import lm as train_lm
from csed_514_project_distributed_training_using_pytorch_tpu.utils.config import LMConfig
assert os.path.realpath(train_lm.__file__).startswith(root + os.sep), train_lm.__file__
TIMING = ("t_s", "ts", "lower_s", "compile_s", "scopes_s", "wall_s", "execute_s", "eval_s", "data_s",
          "log_s", "emit_s", "guard_s", "checkpoint_s", "tick_s", "period_s", "examples_per_s",
          "steps_per_s", "mfu")
FAMILIES = {"lfm2_moe": ("test_hybrid_lm", {}), "nemotron_h": ("test_nemotron_h", {"chunk_size": 16}),
            "kimi_linear": ("test_kimi_linear", {}), "deepseek_v3": ("test_deepseek_v3", {}),
            "evabyte": ("test_evabyte", {"window_size": 16, "chunk_size": 4})}
work = tempfile.mkdtemp(prefix="pr42_")
os.chdir(work)
lines = []
for family, (module, changes) in {**FAMILIES, "pixel": (None, None)}.items():
    tele = f"{family}.jsonl"
    common = dict(mesh="data=1", epochs=1, telemetry=tele, results_dir="", images_dir="images",
                  generate=0, remat=True, seed=5, learning_rate=3e-3)
    if module is None:
        from csed_514_project_distributed_training_using_pytorch_tpu.data import mnist
        import numpy as np
        rng = np.random.default_rng(0)
        split = lambda n: mnist.Dataset(rng.normal(size=(n, 28, 28, 1)).astype(np.float32),
                                        rng.integers(0, 10, n).astype(np.int32), "seeded")
        train_lm.main(LMConfig(**common, batch_size=8, eval_batch=8, embed_dim=32, num_layers=1,
                               num_heads=4, kv_heads=2, rope=True, optimizer="adamw"),
                      datasets=(split(16), split(8)))
    else:
        tests = importlib.import_module(module)
        config = dict(tests.tiny_config(vocab_size=256), **changes)
        with open(f"{family}.json", "w") as fh:
            json.dump({k: v for k, v in config.items() if k != "train"}, fh)
        build = hybrid_lm.from_config
        if family == "kimi_linear":     # the tiling is no key of the file
            hybrid_lm.from_config = lambda *a, **kw: build(*a, **dict(kw, kda_tiling=tests.TILING))
        try:
            train_lm.main(LMConfig(**common, model_config=f"{family}.json", batch_size=8, eval_batch=19,
                                   corpus=os.path.join(root, "tests", "fixtures", "corpus_tiny")))
        finally:
            hybrid_lm.from_config = build
    with open(tele) as fh:
        events = [json.loads(line) for line in fh]
    with open(tele + ".scopes.json", "rb") as fh:
        table = hashlib.sha256(fh.read()).hexdigest()
    kept = [{k: v for k, v in e.items() if k not in TIMING} for e in events
            if e["event"] in ("compile", "epoch")]
    lines.append(json.dumps({"run": family, "scopes_json_sha256": table, "events": kept}, sort_keys=True))
with open(out_path, "w") as fh:
    fh.write("\n".join(lines) + "\n")
print(f"{len(lines)} runs from {root} -> {out_path}")
