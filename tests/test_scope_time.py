"""Device time by the model's own scopes: ``utils.profiling.scope_of`` / ``scope_table``
on op names and on the three families' compiled epoch programs, and the benchmark's
``reducers/scope_time.py`` on a hand-worked trace (PR 35)."""

import functools
import json
import os
import sys

import jax
import jax.numpy as jnp
import pytest
from jax.profiler import ProfileData

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(REPO, "benchmark")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

import harness  # noqa: E402
import xplane  # noqa: E402

from csed_514_project_distributed_training_using_pytorch_tpu.models import (  # noqa: E402
    hybrid_lm,
)
from csed_514_project_distributed_training_using_pytorch_tpu.ops import optim  # noqa: E402
from csed_514_project_distributed_training_using_pytorch_tpu.train.step import (  # noqa: E402
    create_train_state, make_epoch_from_step, make_train_step,
)
from csed_514_project_distributed_training_using_pytorch_tpu.utils import (  # noqa: E402
    profiling, telemetry as T,
)

# -- (a) one op_name -----------------------------------------------------------------------

OP_NAMES = {
    # the four shapes ISSUE 35 quotes: a block under jax.checkpoint inside a scan
    "forward": ("jit(loss)/jvp()/while/body/closed_call/attention/dot_general",
                ("attention", "forward")),
    "recomputed": ("jit(loss)/transpose(jvp())/while/body/closed_call/checkpoint/"
                   "rematted_computation/attention/tanh", ("attention", "recompute")),
    "backward": ("jit(loss)/transpose(jvp())/while/body/closed_call/checkpoint/attention/"
                 "dot_general", ("attention", "backward")),
    "scope inside the wrapper": ("jit(loss)/jvp(head_loss)/reduce_sum",
                                 ("head_loss", "forward")),
    # what the epoch programs compiled for the v5e hold
    "a path inside the wrapper": (
        "jit(epoch)/while/body/closed_call/jvp(moe/route)/jit(take_along_axis)/gather",
        ("moe/route", "forward")),
    "a jitted function inside the wrapper, a kernel's name": (
        "jit(epoch)/while/body/closed_call/jvp(jit(forward))/moe/experts/moe_ffn_fwd/"
        "pallas_call", ("moe/experts/moe_ffn_fwd", "forward")),
    "a transpose inside a scope's wrapper": (
        "jit(epoch)/while/body/closed_call/jvp(head_loss)/transpose(jvp(jit(take_along_axis)"
        "))/scatter-add", ("head_loss", "backward")),
    "a custom rule's backward function": (
        "jit(epoch)/while/body/closed_call/transpose(jvp(jvp()))/checkpoint/jit(backward)/moe/"
        "combine/moe_gather/pallas_call", ("moe/combine/moe_gather", "backward")),
    "vmap and a loop inside a scope": (
        "jit(epoch)/while/body/closed_call/jvp(moe/sort)/jit(searchsorted)/vmap()/closed_call/"
        "while/body/closed_call/add", ("moe/sort", "forward")),
    "an einsum's own name": (
        "jit(epoch)/while/body/closed_call/transpose(jvp(jvp()))/checkpoint/attention/"
        "bhqk,bkhd->bqhd/dot_general", ("attention", "backward")),
    "a cond's arm": ("jit(epoch)/while/body/jvp(TransformerLM)/block_1/attn/flash_fwd/cond/"
                     "branch_1_fun/mul", ("TransformerLM/block/attn/flash_fwd", "forward")),
    # flax pushes its modules' names; instances of one module add up
    "flax": ("jit(train)/TransformerLM/TransformerBlock_3/MultiHeadAttention_0/Dense_0/"
             "dot_general", ("TransformerLM/TransformerBlock/MultiHeadAttention/Dense", "forward")),
    "the optimizer": ("jit(epoch)/while/body/closed_call/optimizer/mul",
                      ("optimizer", "forward")),
    "no scope": ("jit(epoch)/while/body/closed_call/transpose(jvp(jvp()))/checkpoint/mul",
                 (None, "backward")),
    "a primitive alone": ("reduce_sum", (None, "forward")),
    "an argument's name": ("state.velocity[\\'v\\'][\\'layer_4\\'][\\'moe\\']", (None, "forward")),
    "no op_name": (None, (None, None)),
}


@pytest.mark.parametrize("case", list(OP_NAMES))
def test_scope_of(case):
    op_name, want = OP_NAMES[case]
    assert profiling.scope_of(op_name) == want


# -- (b) a program's text -----------------------------------------------------------------

TEXT = '''HloModule jit_step, is_scheduled=true, entry_computation_layout={(f32[8]{0})->f32[8]{0}}

%fused_computation.1 (param_0: f32[8]) -> f32[8] {
  %param_0 = f32[8]{0} parameter(0)
  %multiply.1 = f32[8]{0} multiply(f32[8]{0} %param_0, f32[8]{0} %param_0), metadata={op_name="jit(step)/jvp(attention)/mul" source_file="a.py" source_line=3}
  ROOT %add.1 = f32[8]{0} add(f32[8]{0} %multiply.1, f32[8]{0} %param_0), metadata={op_name="jit(step)/jvp(dense_ff)/add"}
}

%fused_computation.2 (param_0.1: f32[8]) -> f32[8] {
  %param_0.1 = f32[8]{0} parameter(0)
  ROOT %negate.1 = f32[8]{0} negate(f32[8]{0} %param_0.1), metadata={op_name="jit(step)/optimizer/neg"}
}

%fused_computation.4 (param_0.3: f32[]) -> f32[8] {
  %param_0.3 = f32[] parameter(0)
  ROOT %broadcast.1 = f32[8]{0} broadcast(f32[] %param_0.3), dimensions={}
}

%fused_computation.3 (param_0.2: f32[8], param_1.2: s32[8]) -> f32[8] {
  %param_0.2 = f32[8]{0} parameter(0)
  %param_1.2 = s32[8]{0} parameter(1)
  %fusion.20 = f32[8]{0} fusion(f32[] %constant.3), kind=kLoop, calls=%fused_computation.4
  %reshape.1 = f32[8,1]{1,0} reshape(f32[8]{0} %param_0.2), metadata={op_name="jit(step)/transpose(jvp(moe/route))/add_any"}
  ROOT %scatter.1 = f32[8]{0} scatter(f32[8]{0} %fusion.20, s32[8]{0} %param_1.2, f32[8,1]{1,0} %reshape.1), to_apply=%region_0.1
}

%region_0.1 (a: f32[], b: f32[]) -> f32[] {
  %a = f32[] parameter(0)
  %b = f32[] parameter(1)
  ROOT %add.2 = f32[] add(f32[] %a, f32[] %b), metadata={op_name="reduce_sum"}
}

%body.1 (arg: (s32[], f32[8])) -> (s32[], f32[8]) {
  %arg = (s32[], f32[8]{0}) parameter(0)
  %get-tuple-element.1 = f32[8]{0} get-tuple-element((s32[], f32[8]{0}) %arg), index=1
  %fusion.12 = f32[8]{0} fusion(f32[8]{0} %get-tuple-element.1), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(step)/while/body/jvp(dense_ff)/add"}
  %reduce.3 = f32[] reduce(f32[8]{0} %fusion.12, f32[] %constant.1), dimensions={0}, to_apply=%region_0.1, metadata={op_name="jit(step)/while/body/transpose(jvp(head_loss))/reduce_sum"}
  %copy.7 = f32[8]{0:T(128)} copy(f32[8]{0} %fusion.12)
  ROOT %tuple.1 = (s32[], f32[8]{0}) tuple(s32[] %get-tuple-element.0, f32[8]{0} %copy.7)
}

%cond.1 (arg.1: (s32[], f32[8])) -> pred[] {
  %arg.1 = (s32[], f32[8]{0}) parameter(0)
  ROOT %compare.1 = pred[] compare(s32[] %get-tuple-element.2, s32[] %constant.2), direction=LT, metadata={op_name="jit(step)/while/cond/lt"}
}

ENTRY %main.9 (Arg_0.1: f32[8]) -> f32[8] {
  %Arg_0.1 = f32[8]{0} parameter(0), metadata={op_name="x"}
  %while.1 = (s32[], f32[8]{0:T(128)}) while((s32[], f32[8]{0}) %tuple.0), condition=%cond.1, body=%body.1, metadata={op_name="jit(step)/while"}
  %fusion.14 = f32[8]{0} fusion(f32[8]{0} %get-tuple-element.3, s32[8]{0} %iota.1), kind=kCustom, calls=%fused_computation.3
  ROOT %fusion.13 = f32[8]{0} fusion(f32[8]{0} %fusion.14), kind=kLoop, calls=%fused_computation.2, metadata={op_name="jit(step)/optimizer/neg"}
}
'''


def test_scope_table_reads_every_computation_that_runs_as_ops():
    table = profiling.scope_table(TEXT, detail=True)
    assert table["module"] == "jit_step"
    assert table["ops"] == {
        "fusion.12": ["dense_ff", "forward"],       # its root's, though it holds two scopes
        "reduce.3": ["head_loss", "backward"],
        "copy.7": [None, None],                     # a copy the compiler placed: no op_name
        "compare.1": [None, "forward"],
        "while.1": [None, "forward"],
        "fusion.14": ["moe/route", "backward"],     # no op_name of its own: its contents'
        "fusion.13": ["optimizer", "forward"],
    }   # no parameter, tuple or get-tuple-element, and nothing of a fused computation
    #     (fusion.20 is inside fusion.14) or of a reduction's scalar function
    assert table["mixed"] == ["fusion.12"]
    assert table["detail"]["while.1"] == {"shape": "(s32[], f32[8]{0:T(128)})",
                                          "op_name": "jit(step)/while"}
    assert table["detail"]["copy.7"] == {"shape": "f32[8]{0:T(128)}", "op_name": None}
    assert "detail" not in profiling.scope_table(TEXT)


def test_the_table_is_written_one_line_an_instruction(tmp_path):
    table = profiling.scope_table(TEXT)
    said = T.write_scope_table(str(tmp_path / "t.jsonl"), table, steps_per_call=8)
    assert said == {"path": str(tmp_path / "t.jsonl.scopes.json"), "module": "jit_step",
                    "instructions": 7, "named_share": 4 / 7, "mixed": 1,
                    "top_scopes": ["dense_ff", "head_loss", "moe", "optimizer"]}
    with open(said["path"]) as fh:
        lines = fh.read().splitlines()
    assert '"reduce.3": ["head_loss", "backward"],' in lines
    assert json.loads("\n".join(lines)) == dict(table, steps_per_call=8)
    event = T.compile_event("epoch", {"lower_s": 1.0, "compile_s": 2.0, "scopes_s": 0.25},
                            scopes=said)
    assert event["scopes"] == said and event["scopes_s"] == 0.25


def test_aot_compile_hands_over_the_table_of_the_executable():
    def loss(x):
        with jax.named_scope("square"):
            return jnp.sum(x * x)

    compiled, aot = T.aot_compile(jax.jit(jax.grad(loss)), jnp.ones((8, 8)))
    assert compiled is not None and aot["scopes_s"] > 0
    assert aot["scopes"]["module"] == "jit_loss"
    assert "square" in {scope for scope, _ in aot["scopes"]["ops"].values()}


HAZARD = '''
import sys, jax, jax.numpy as jnp
sys.path.insert(0, {repo!r})
from csed_514_project_distributed_training_using_pytorch_tpu.utils import telemetry as T
jax.config.update("jax_compilation_cache_dir", {cache!r})
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
assert not jax.config.jax_compilation_cache_include_metadata_in_key
def tree(name):
    def f(x):
        with jax.named_scope(name):
            return jnp.sum(jnp.tanh(x) * x)
    return jax.jit(f)
for name in ("older_tree", "this_tree"):
    compiled, aot = T.aot_compile(tree(name), jnp.ones((64, 64)))
    print(name, sorted({{scope for scope, _ in aot["scopes"]["ops"].values() if scope}}))
'''


def test_a_warm_cache_serves_an_older_trees_names_and_the_table_says_so(tmp_path):
    """Two programs that differ in a scope's name alone lower to the same text but for
    metadata, which the persistent cache's key leaves out: the second is handed the
    first's executable, names and numbering and all. The table is read from the executable
    that runs, so it names the older tree's scope: it describes what a trace of this run
    would show, and the ``compile`` event's ``top_scopes`` says whose names those are."""
    import subprocess
    out = subprocess.run(
        [sys.executable, "-c", HAZARD.format(repo=REPO, cache=str(tmp_path / "cache"))],
        env=dict(os.environ, JAX_PLATFORMS="cpu"), capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr[-2000:]
    assert out.stdout.splitlines() == ["older_tree ['older_tree']", "this_tree ['older_tree']"]


# -- (c) the families' epoch programs -------------------------------------------------

FAMILIES = {"lfm2_moe": "test_hybrid_lm", "nemotron_h": "test_nemotron_h",
            "kimi_linear": "test_kimi_linear", "falcon_h1": "test_falcon_h1"}
# Of a CPU program's instructions the share with a scope (0.69-0.79 read: the rest are
# copies and loop plumbing the compiler placed, with no op_name), and of those that carry
# an op_name (0.970-0.986 read: the feed's gather and the loop's counters have none).
FLOOR, FLOOR_OF_NAMED_OPS = 0.6, 0.95


@functools.lru_cache(maxsize=None)     # a family's program compiles once a run of this file
def _epoch_table(family: str, remat: bool) -> tuple[hybrid_lm.HybridLM, dict]:
    """The family's tiny model (its own test file's), two steps of batch 2 as one scanned
    epoch program built as ``train/lm.py`` builds it, compiled on the CPU."""
    import importlib
    tiny = importlib.import_module(FAMILIES[family])
    model, _ = tiny.build(tiny.tiny_config(), remat=remat)
    seq, batch, steps = tiny.SEQ, 2, 2
    opt = optim.freeze(optim.make_optimizer("adamw", learning_rate=1e-6, momentum=0.5,
                                            weight_decay=0.01), hybrid_lm.is_frozen)
    state = jax.eval_shape(lambda: create_train_state(
        model, jax.random.PRNGKey(0), sample_input_shape=(1, seq), optimizer=opt))
    step = make_train_step(
        model, learning_rate=1e-6, momentum=0.5, optimizer=opt, clip_grad_norm=1.0,
        loss_fn=lambda params, xs, ys, rng: model.loss(params, xs), loss_has_aux=True,
        after_update=model.rebalance if model.router_bias_update_rate else None)
    epoch = jax.jit(make_epoch_from_step(step, aux=True))
    n = batch * steps
    compiled = epoch.lower(
        state, jax.ShapeDtypeStruct((n, seq), jnp.int32), jax.ShapeDtypeStruct((n,), jnp.int32),
        jax.ShapeDtypeStruct((steps, batch), jnp.int32),
        jax.eval_shape(lambda: jax.random.PRNGKey(1))).compile()
    return model, profiling.scope_table(compiled.as_text())


def _scopes_of(model: hybrid_lm.HybridLM) -> set[str]:
    """Every scope the model's layers can produce, and the three around them."""
    want = {"optimizer", "embed", "final_norm", "head_loss"}
    for i, kind in enumerate(model.layer_types):
        if kind != "moe":
            want.add(hybrid_lm.MIXER_SCOPES[kind])
        if kind in hybrid_lm.LAYER_KINDS and not model.is_sparse(i):
            want.add("dense_ff")
        if model.is_sparse(i):
            want |= {"moe/norm", "moe/route", "moe/sort", "moe/experts", "moe/combine"}
            if model.moe_latent_size:
                want.add("moe/latent")
            if model.shared_expert_size:
                want.add("moe/shared")
    for mixer, scan in (("mamba_mixer", "ssd"), ("kda_mixer", "kda")):
        if mixer in want:       # the scan's own scope, opened in ops/ssm.py and ops/kda.py
            want.add(f"{mixer}/{scan}")
    if "parallel_mixer" in want:    # its two branches, the scan and the rotation under them
        want |= {"parallel_mixer/ssm/ssd", "parallel_mixer/attention/rotary"}
    return want


@pytest.mark.parametrize("family", list(FAMILIES))
def test_an_epoch_program_names_every_scope_and_pass(family):
    model, table = _epoch_table(family, remat=True)
    ops = table["ops"]
    assert table["module"] == "jit_epoch"
    found = {scope for scope, _ in ops.values() if scope}
    under = lambda want: any(s == want or s.startswith(want + "/") for s in found)
    assert [s for s in sorted(_scopes_of(model)) if not under(s)] == []
    assert {which for _, which in ops.values() if which} == set(profiling.PASSES)
    # a recomputed instruction is a block's: the optimizer, the embedding and the head
    # are outside jax.checkpoint
    recomputed = {scope.split("/")[0] for scope, which in ops.values()
                  if which == "recompute" and scope}
    assert recomputed and not recomputed & {"optimizer", "embed", "final_norm", "head_loss"}
    named = sum(1 for scope, _ in ops.values() if scope)
    assert named / len(ops) > FLOOR, named / len(ops)
    with_op_name = sum(1 for _, which in ops.values() if which)
    assert named / with_op_name > FLOOR_OF_NAMED_OPS, named / with_op_name


@pytest.mark.parametrize("family", list(FAMILIES))
def test_the_parallel_blocks_scopes_are_its_own_stacks_alone(family):
    """``parallel_mixer`` and its branches ``ssm`` and ``attention`` name a ``falcon_h1``
    stack's mixers, which have no ``mamba_mixer`` or top-level ``attention`` of their own;
    no other family's table holds them."""
    _, table = _epoch_table(family, remat=True)
    found = {scope for scope, _ in table["ops"].values() if scope}
    branches = {s for s in found if s.split("/")[0] == "parallel_mixer"}
    if family == "falcon_h1":
        assert {"/".join(s.split("/")[:2]) for s in branches} >= {
            "parallel_mixer", "parallel_mixer/ssm", "parallel_mixer/attention"}
        assert not any(s.split("/")[0] in ("mamba_mixer", "attention") for s in found)
    else:
        assert not branches


@pytest.mark.parametrize("family", list(FAMILIES))
def test_nothing_is_recomputed_without_remat(family):
    _, table = _epoch_table(family, remat=False)
    passes = {which for _, which in table["ops"].values() if which}
    assert passes == {"forward", "backward"}


# -- (d) the benchmark's reducer on a hand-written trace -----------------------------------

# One device plane, times in us. XLA Modules: jit_epoch runs over [0, 100), jit_evaluate
# over [120, 150). XLA Ops, inside jit_epoch: while.1 spans [0, 100) and its body's ops
# fusion.12 [5, 25), fusion.13 [30, 40), kda_bwd.2 [40, 70), copy.7 [70, 75),
# multiply_subtract_fusion.3 [75, 90) and custom-call.9 [90, 95), which the table lacks; so
# while.1's self time is 100 - 85 = 15. Inside jit_evaluate: fusion.12 [120, 140) (the name
# the epoch program has too) and copy.7 [140, 145). Outside every run: copy.99 [160, 162).
def _events(*spans):
    return " ".join(f"events {{ metadata_id: {i} offset_ps: {lo}000000 duration_ps: "
                    f"{hi - lo}000000 }}" for i, lo, hi in spans)


HAND = f'''
planes {{ id: 1 name: "/device:TPU:0"
  lines {{ id: 1 name: "XLA Modules" timestamp_ns: 0
    {_events((20, 0, 100), (21, 120, 150))} }}
  lines {{ id: 2 name: "XLA Ops" timestamp_ns: 0
    {_events((1, 0, 100), (2, 5, 25), (3, 30, 40), (4, 40, 70), (5, 70, 75), (6, 75, 90),
             (7, 90, 95), (2, 120, 140), (5, 140, 145), (8, 160, 162))} }}
  event_metadata {{ key: 1 value {{ id: 1 name: "%while.1 = (s32[], f32[8]) while(...)" }} }}
  event_metadata {{ key: 2 value {{ id: 2 name: "%fusion.12 = f32[8] fusion(...)" }} }}
  event_metadata {{ key: 3 value {{ id: 3 name: "%fusion.13 = f32[8] fusion(...)" }} }}
  event_metadata {{ key: 4 value {{ id: 4 name: "%kda_bwd.2 = f32[8] custom-call(...)" }} }}
  event_metadata {{ key: 5 value {{ id: 5 name: "%copy.7 = f32[8] copy(...)" }} }}
  event_metadata {{ key: 6 value {{ id: 6 name: "%multiply_subtract_fusion.3 = f32[8] fusion(...)" }} }}
  event_metadata {{ key: 7 value {{ id: 7 name: "%custom-call.9 = f32[8] custom-call(...)" }} }}
  event_metadata {{ key: 8 value {{ id: 8 name: "%copy.99 = f32[8] copy(...)" }} }}
  event_metadata {{ key: 20 value {{ id: 20 name: "jit_epoch(111)" }} }}
  event_metadata {{ key: 21 value {{ id: 21 name: "jit_evaluate(222)" }} }} }}
planes {{ id: 2 name: "/host:CPU" }}
'''
TABLE = {"module": "jit_epoch", "steps_per_call": 2,
         "mixed": ["multiply_subtract_fusion.3"],
         "ops": {"while.1": [None, "forward"], "fusion.12": ["attention", "forward"],
                 "fusion.13": ["attention", "recompute"],
                 "kda_bwd.2": ["kda_mixer/kda/kda_bwd", "backward"], "copy.7": [None, None],
                 "multiply_subtract_fusion.3": ["optimizer", "forward"],
                 "never_ran.1": ["moe/route", "forward"]}}
METRICS = ["scope_named_share", "recompute_share", "moe_routing_ms", "head_loss_ms",
           "mixer_outside_kernels_ms"]


def _profile(text):
    return ProfileData.from_serialized_xspace(
        ProfileData.text_proto_to_serialized_xspace(text))


def _scope_time():
    return harness.load_module(os.path.join(BENCH, "reducers", "scope_time.py"),
                               "bench_reducer_scope_time")


def test_scope_time_by_hand():
    scope_time = _scope_time()
    profile = _profile(HAND)
    times, calls = scope_time.program_times(profile)
    assert calls == {"jit_epoch": 1, "jit_evaluate": 1}
    assert times[("jit_epoch", "while.1")] == 15_000
    assert times[("jit_epoch", "fusion.12")] == times[("jit_evaluate", "fusion.12")] == 20_000
    joined = scope_time.join(times, TABLE)
    scopes, unnamed = scope_time.by_scope(joined)
    assert scopes == {"attention": {"forward": 20_000, "recompute": 10_000},
                      "kda_mixer/kda/kda_bwd": {"backward": 30_000},
                      "optimizer": {"forward": 15_000}}
    assert unnamed == {"while": 15_000, "copy": 5_000}
    assert joined["not_in_table"] == {"custom-call": 5_000}
    assert joined["other_programs"] == {"jit_evaluate": 25_000, "": 2_000}
    assert joined["mixed_ns"] == 15_000
    assert scope_time.epoch_ns(joined) == 100_000
    # every nanosecond of the line lands in exactly one place
    plain = xplane.self_times(next(iter(xplane.device_op_events(profile).values())))
    assert sum(plain.values()) == 127_000 == (
        sum(sum(p.values()) for p in scopes.values()) + sum(unnamed.values())
        + sum(joined["not_in_table"].values()) + sum(joined["other_programs"].values()))
    select = lambda **params: scope_time.selected_ns(joined, **params)
    assert select(named=True) == 75_000
    assert select(passes=["recompute"]) == 10_000
    assert select(scopes=["kda_mixer"]) == 30_000       # a prefix selects what is under it
    assert select(scopes=["kda"]) == 0                  # and nothing that only starts like it
    assert select(scopes=["kda_mixer", "attention"], exclude_ops=["kda_bwd"]) == 30_000
    assert select(passes=["forward"]) == 50_000         # unnamed instructions have a pass too


def _traced_run(tmp_path, monkeypatch, scope_time, table=TABLE):
    work = tmp_path / "work"
    work.mkdir(exist_ok=True)
    if table is not None:
        T.write_scope_table(str(work / "telemetry.jsonl"),
                            {k: table[k] for k in ("module", "ops", "mixed")},
                            steps_per_call=table["steps_per_call"])
    monkeypatch.setattr(scope_time.xplane, "find_trace", lambda d: d)
    monkeypatch.setattr(scope_time.xplane, "load", lambda path: _profile(HAND))
    return harness.Observations(trace_dir=str(work / "trace"), trace_units={"steps": 2},
                                trace={"devices": 1})


def test_scope_time_metrics_print_once_and_keep_the_table(tmp_path, monkeypatch, capsys):
    scope_time = _scope_time()
    obs = _traced_run(tmp_path, monkeypatch, scope_time)
    values = {}
    for name in METRICS:        # as harness.layer_metrics does: the module anew each time
        with open(os.path.join(BENCH, "layer_metrics", name + ".json")) as fh:
            values[name] = _scope_time().read(obs, **json.load(fh)["params"])
    assert values == {"scope_named_share": pytest.approx(75.0),
                      "recompute_share": pytest.approx(10.0),
                      "moe_routing_ms": 0.0, "head_loss_ms": 0.0,
                      "mixer_outside_kernels_ms": 0.0}
    out = capsys.readouterr().out
    assert out.count("device time by scope") == 1
    assert out.startswith(
        "device time by scope (jit_epoch, ms a step over 2 steps): attention 0.015 "
        "(f 0.010 r 0.005 b 0.000), kda_mixer/kda/kda_bwd 0.015 (f 0.000 r 0.000 b 0.015), "
        "optimizer 0.007 (f 0.007 r 0.000 b 0.000), unnamed 0.010, not in the table 0.003, "
        "mixed fusions 0.007; other programs: jit_evaluate 0.013, (no program) 0.001")
    with open(tmp_path / "work" / "scope_time.json") as fh:
        kept = json.load(fh)
    assert kept["epoch_program_ns"] == 100_000 and kept["steps"] == 2
    assert kept["scopes"]["attention"] == {"forward": 20_000, "recompute": 10_000}
    assert kept["unnamed_by_op"] == {"while": 15_000, "copy": 5_000}
    assert kept["not_in_table_by_op"] == {"custom-call": 5_000}
    assert kept["other_programs_ns"] == {"jit_evaluate": 25_000, "": 2_000}
    assert kept["program_runs"] == {"jit_epoch": 1, "jit_evaluate": 1}
    assert ["attention", "forward", "fusion", 20_000] in kept["rows"]


@pytest.mark.parametrize("case", ["a program older than the table", "another program's table",
                                  "no trace", "no device plane"])
def test_scope_time_reads_nothing(tmp_path, monkeypatch, case):
    scope_time = _scope_time()
    table = {"a program older than the table": None,
             "another program's table": dict(TABLE, module="jit_step")}.get(case, TABLE)
    obs = _traced_run(tmp_path, monkeypatch, scope_time, table)
    if case == "no trace":
        obs.trace = None
    if case == "no device plane":
        monkeypatch.setattr(scope_time.xplane, "load",
                            lambda path: _profile('planes { id: 1 name: "/host:CPU" }'))
    assert scope_time.read(obs, named=True, **{"as": "share"}) is None
    assert scope_time.read(obs, scopes=["head_loss"], **{"as": "ms_per_step"}) is None


def test_scope_time_as_a_script(tmp_path, monkeypatch, capsys):
    scope_time = _scope_time()
    obs = _traced_run(tmp_path, monkeypatch, scope_time)
    table = os.path.join(os.path.dirname(obs.trace_dir), "telemetry.jsonl.scopes.json")
    assert scope_time.main([obs.trace_dir, table]) == 0
    out = capsys.readouterr().out
    assert out.startswith("device time by scope (jit_epoch, ms a step over 2 steps): "
                          "attention 0.015 (f 0.010 r 0.005 b 0.000)")
    assert "1 runs of jit_epoch, 0.050 ms a step of device self time; named 75.00 %, " \
           "recompute 10.00 %" in out


# -- (e) the manifest ------------------------------------------------------------------------


@pytest.mark.parametrize("name", METRICS)
def test_the_manifest_lists_the_metric_with_its_file(name):
    with open(os.path.join(REPO, "BENCHMARK.json")) as fh:
        manifest = json.load(fh)
    (entry,) = [m for m in manifest["per_layer"] if m["name"] == name]
    with open(os.path.join(BENCH, "layer_metrics", name + ".json")) as fh:
        spec = json.load(fh)
    assert spec["reducer"] == "scope_time" and spec["params"]["as"] in ("share", "ms_per_step")
    assert (spec["layer"], spec["unit"], spec["moves"]) == (
        entry["layer"], entry["unit"], entry["moves"])
    assert (entry["source"], entry["moves"]) == ("device_trace", "train_examples_per_s")
    cells = {w["name"] for w in manifest["workloads"]}
    assert entry["workloads"] and set(entry["workloads"]) <= cells
    # appended in this order, after the metrics the benchmark had before them (by
    # membership: later configurations append their own metrics after these)
    names = [m["name"] for m in manifest["per_layer"]]
    assert [n for n in names if n in METRICS] == METRICS
    assert names.index(METRICS[0]) > names.index("kimi_expert_load_imbalance")
