"""The seam between a model and ``train/lm.py`` (``models.Trainee``): the trainer asks a
model for its training view and holds no model class's name, and the ``compile`` event
carries the model's ``plans`` whole. Nothing here compiles or trains: ``train.lm.main``
runs with no epoch and its ahead-of-time compile replaced by a trace."""

import ast
import dataclasses
import importlib
import json
import os
import sys

import jax
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [REPO, os.path.join(REPO, "tests")]

from csed_514_project_distributed_training_using_pytorch_tpu import ops  # noqa: E402
from csed_514_project_distributed_training_using_pytorch_tpu.data import mnist  # noqa: E402
from csed_514_project_distributed_training_using_pytorch_tpu.models import (  # noqa: E402
    Trainee, hybrid_lm, lm as lm_mod,
)
from csed_514_project_distributed_training_using_pytorch_tpu.train import (  # noqa: E402
    lm as train_lm,
)
from csed_514_project_distributed_training_using_pytorch_tpu.utils import (  # noqa: E402
    telemetry as T,
)
from csed_514_project_distributed_training_using_pytorch_tpu.utils.config import (  # noqa: E402
    LMConfig,
)

CORPUS = os.path.join(REPO, "tests", "fixtures", "corpus_tiny")     # 256 ids, rows of 64
# family -> (the test module whose ``tiny_config`` cuts its file, what the corpus's rows force)
FAMILIES = {"lfm2_moe": ("test_hybrid_lm", {}),
            "nemotron_h": ("test_nemotron_h", {"chunk_size": 16}),
            "kimi_linear": ("test_kimi_linear", {}),
            "deepseek_v3": ("test_deepseek_v3", {}),
            "evabyte": ("test_evabyte", {"window_size": 16, "chunk_size": 4}),
            "qwen3_next": ("test_qwen3_next", {}),
            "falcon_h1": ("test_falcon_h1", {})}
TRAIN_LM = os.path.join(os.path.dirname(train_lm.__file__), "lm.py")


def traced_compile_event(tmp_path, monkeypatch, datasets=None, **flags):
    """``train.lm.main`` up to its epoch loop, the epoch program traced and not compiled:
    the model it built, the jaxpr the plans read and the ``compile`` event it wrote."""
    seen = {}

    def aot_compile(jit_fn, *args):
        seen["jaxpr"] = jit_fn.trace(*args).jaxpr
        return jit_fn, {"lower_s": 0.0, "compile_s": 0.0, "flops": None, "scopes_s": 0.0,
                        "jaxpr": seen["jaxpr"],
                        "scopes": {"module": "jit_epoch", "ops": {}, "mixed": []}}

    def spy(cls):
        build = cls.trainee

        def trainee(self, **knobs):
            seen["model"] = self
            return build(self, **knobs)

        monkeypatch.setattr(cls, "trainee", trainee)

    monkeypatch.setattr(T, "aot_compile", aot_compile)
    create = train_lm.create_train_state    # a state of zeros: no leaf's program is compiled
    monkeypatch.setattr(train_lm, "create_train_state", lambda *a, **kw: jax.tree_util.tree_map(
        lambda leaf: np.zeros(leaf.shape, leaf.dtype), jax.eval_shape(lambda: create(*a, **kw))))
    spy(hybrid_lm.HybridLM)
    spy(lm_mod.TransformerLM)
    tele = tmp_path / "t.jsonl"
    train_lm.main(LMConfig(mesh="data=1", epochs=0, batch_size=8, telemetry=str(tele),
                           results_dir="", images_dir=str(tmp_path / "images"), generate=0,
                           remat=True, **flags), datasets=datasets)
    (event,) = [e for e in map(json.loads, tele.read_text().splitlines())
                if e["event"] == "compile"]
    return seen["model"], seen["jaxpr"], event


@pytest.mark.parametrize("family", [*FAMILIES, "pixel"])
def test_the_compile_event_carries_the_models_plans_whole(family, tmp_path, monkeypatch):
    if family == "pixel":
        rng = np.random.default_rng(0)
        split = lambda n: mnist.Dataset(rng.normal(size=(n, 28, 28, 1)).astype(np.float32),
                                        rng.integers(0, 10, n).astype(np.int32), "seeded")
        model, jaxpr, event = traced_compile_event(
            tmp_path, monkeypatch, datasets=(split(8), split(8)), eval_batch=8, embed_dim=32,
            num_layers=1, num_heads=4, kv_heads=2)
        assert isinstance(model, lm_mod.TransformerLM)
        said = {}
    else:
        module, forced = FAMILIES[family]
        tests = importlib.import_module(module)
        path = tmp_path / "tiny.json"
        path.write_text(json.dumps(dict(tests.tiny_config(vocab_size=256), **forced)))
        if family in ("kimi_linear", "qwen3_next"):     # the tiling is no key of the file
            build = hybrid_lm.from_config
            monkeypatch.setattr(hybrid_lm, "from_config", lambda *a, **kw: build(
                *a, **dict(kw, kda_tiling=tests.TILING)))
        model, jaxpr, event = traced_compile_event(
            tmp_path, monkeypatch, model_config=str(path), corpus=CORPUS, eval_batch=19)
        tokens = 8 * model.seq_len
        said = {"experts": model.expert_plan(tokens), "recompute": model.recompute_plan(jaxpr),
                "head_products": model.head_products(jaxpr, tokens), "ssm": model.ssm_plan(),
                "kda": model.kda_plan(), "gdn": model.gdn_plan(), "eva": model.eva_plan(),
                "norm": model.norm_plan(),
                # the forward multipliers the program applied, by name: a ``falcon_h1`` file's
                "multipliers": model.multipliers and dataclasses.asdict(model.multipliers)}
        assert (said["multipliers"] is None) == (family != "falcon_h1")
        assert model.plans(jaxpr, tokens) == said
        assert said["recompute"]["kept"] and said["head_products"] == 3
    view = model.trainee()
    assert isinstance(view, Trainee) and view.plans(jaxpr, 8 * model.seq_len) == said
    fixed = set(T.compile_event("epoch", {})) | {"t_s"}
    assert set(event) == fixed | set(said) and not fixed & set(said)
    assert {key: event[key] for key in said} == json.loads(json.dumps(said))
    # the attention entry is the model's shape through the dispatcher, and its own fields
    if view.attention_shape is None:        # no dispatch plan: the model's own fields alone
        assert event["attention"] == (view.attention_fields or None)
    else:
        heads, head_dim, value_dim = view.attention_shape
        assert event["attention"] == {**ops.dispatch_plan(
            (8, model.seq_len, heads, head_dim), causal=True, value_dim=value_dim),
            **view.attention_fields}


@pytest.mark.parametrize("flag", ["label_smoothing", "dropout_rate"])
def test_a_model_from_a_file_refuses_the_knobs_it_would_ignore(flag, tmp_path):
    tests = importlib.import_module("test_hybrid_lm")
    path = tmp_path / "tiny.json"
    path.write_text(json.dumps(tests.tiny_config(vocab_size=256)))
    with pytest.raises(ValueError, match="--label-smoothing and no --dropout-rate"):
        train_lm.main(LMConfig(model_config=str(path), corpus=CORPUS, mesh="data=1",
                               epochs=0, batch_size=8, results_dir="", generate=0,
                               **{flag: 0.1}))


def test_both_views_answer_the_same_questions_and_the_pixel_path_stays_plain():
    pixel = lm_mod.TransformerLM(seq_len=16, embed_dim=32, num_heads=4).trainee(
        deterministic=True, label_smoothing=0.1)
    tests = importlib.import_module("test_hybrid_lm")
    held = hybrid_lm.from_config(tests.tiny_config(), vocab_size=tests.VOCAB, seq_len=tests.SEQ,
                                 expert_block=8).trainee()
    assert (pixel.has_aux, pixel.after_update, pixel.is_frozen, pixel.expert_block) == (
        False, None, None, None)        # its step, its state tree: what they were
    assert pixel.targets_per_seq == 16 and pixel.attention_shape == (4, 8, None)
    assert held.has_aux and held.is_frozen is hybrid_lm.is_frozen
    assert held.after_update is None and held.expert_block == 8     # no bias update rate
    assert held.targets_per_seq == tests.SEQ - 1 and held.attention_shape == (4, 8, 8)
    assert held.attention_fields == {"rope_dim": 8, "rope_pairing": "half_split",
                                     "rope_theta": 1e6, "rotation": "permutation"}


def test_the_trainer_names_no_model_class():
    """``train/lm.py`` refers to ``hybrid_lm`` where it builds the model from its file and
    nowhere else, and no ``isinstance`` there is against a name of ``models/``."""
    with open(TRAIN_LM) as fh:
        tree = ast.parse(fh.read())
    from_models = {alias.asname or alias.name for node in ast.walk(tree)
                   if isinstance(node, ast.ImportFrom) and node.module.endswith(".models")
                   for alias in node.names}
    assert {"hybrid_lm", "lm_mod"} <= from_models
    uses = [node for node in ast.walk(tree) if isinstance(node, ast.Attribute)
            and isinstance(node.value, ast.Name) and node.value.id == "hybrid_lm"]
    assert [node.attr for node in uses] == ["from_config_file"]
    for call in ast.walk(tree):
        if isinstance(call, ast.Call) and getattr(call.func, "id", "") == "isinstance":
            named = {node.id for node in ast.walk(call.args[1]) if isinstance(node, ast.Name)}
            assert not named & from_models, ast.unparse(call)
    assert not any(isinstance(node, ast.Name) and node.id == "hybrid" for node in ast.walk(tree))
