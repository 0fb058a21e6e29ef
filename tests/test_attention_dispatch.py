"""The attention dispatcher: which core a call gets (``ops.dispatch_plan``, by the size of
the float32 score tensor), the padded causal path of the flash kernels at unaligned S, and
the LM trainer's model and ``compile`` event on top of them.

CPU interpret mode, small shapes; the predicate's table holds the recorded chip shapes and
needs no kernel run.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from csed_514_project_distributed_training_using_pytorch_tpu import ops
from csed_514_project_distributed_training_using_pytorch_tpu.ops import (
    pallas_attention as pa,
)
from csed_514_project_distributed_training_using_pytorch_tpu.ops.attention import (
    full_attention,
)


def _qkv(s, *, b=2, h=2, d=32, dtype=jnp.float32, seed=0):
    """``d`` is the head width, or ``(key width, value width)`` where the two differ."""
    rng = np.random.default_rng(seed)
    dk, dv = d if isinstance(d, tuple) else (d, d)
    return tuple(jnp.asarray(rng.normal(size=(b, s, h, width)), dtype)
                 for width in (dk, dk, dv))


def _grads(attn, q, k, v, **kw):
    loss = lambda q, k, v: jnp.sum(jnp.sin(attn(q, k, v, causal=True, **kw)
                                           .astype(jnp.float32)))
    return jax.grad(loss, argnums=(0, 1, 2))(q, k, v)


# float32 is exact to round-off in interpret mode; bf16 operands at the tolerance
# tests/test_pallas_attention.py holds the bf16 kernels to.
_TOL = {jnp.float32: (dict(rtol=1e-5, atol=1e-5), dict(rtol=1e-4, atol=2e-5)),
        jnp.bfloat16: (dict(rtol=2e-2, atol=2e-2), dict(rtol=5e-2, atol=5e-2))}


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize("s,kw", [(200, {}), (300, {}), (200, {"window": 48}),
                                  (200, {"block": 256}), (200, {"d": (192, 128)})],
                         ids=["s200", "s300", "s200-window48", "s200-block256",
                              "s200-keys192-values128"])
def test_padded_causal_flash_matches_dense(s, kw, dtype):
    """A causal call at an S the kernels cannot tile is padded at the tail, run
    through them and sliced: output and q/k/v gradients are the dense core's."""
    kw = dict(kw)
    q, k, v = _qkv(s, dtype=dtype, seed=s, d=kw.pop("d", 32))
    dense_kw = {"window": kw["window"]} if "window" in kw else {}
    q32, k32, v32 = (x.astype(jnp.float32) for x in (q, k, v))
    out_tol, grad_tol = _TOL[dtype]
    out = pa.flash_attention(q, k, v, causal=True, **kw)
    assert out.shape == v.shape and out.dtype == q.dtype
    np.testing.assert_allclose(
        np.asarray(out.astype(jnp.float32)),
        np.asarray(full_attention(q32, k32, v32, causal=True, **dense_kw)), **out_tol)
    want = _grads(full_attention, q32, k32, v32, **dense_kw)
    got = _grads(pa.flash_attention, q, k, v, **kw)
    for name, g, r in zip("qkv", got, want):
        assert g.shape == r.shape
        np.testing.assert_allclose(np.asarray(g.astype(jnp.float32)), np.asarray(r),
                                   err_msg=f"d{name}", **grad_tol)


def test_padded_rows_receive_exactly_zero_gradient():
    """What makes tail padding exact: a loss that reads only the first ``s`` rows
    of a padded call gives rows ``s:`` of dq, dk and dv exactly 0 (padded keys are
    masked for every real query; padded queries carry dout = Δ = 0)."""
    s, padded = 200, 256
    q, k, v = _qkv(padded, seed=3)
    loss = lambda q, k, v: jnp.sum(
        jnp.sin(pa.flash_attention(q, k, v, causal=True)[:, :s]))
    for name, g in zip("qkv", jax.grad(loss, argnums=(0, 1, 2))(q, k, v)):
        tail = np.asarray(g[:, s:])
        assert tail.shape[1] == padded - s
        assert not tail.any(), f"d{name} has a nonzero padded row"
        assert np.asarray(g[:, :s]).any()


def test_non_causal_unaligned_still_refused_by_the_kernels():
    q, k, v = _qkv(200)
    with pytest.raises(ValueError, match="divisible by"):
        pa.flash_attention(q, k, v)


# The recorded shapes (B, S, H, D), the mask, and the core each gets. The byte
# figures are B*H*S*S*4: bench_results/hw_r4/bench_attention_tpu.jsonl (B = 1),
# hw_r3/bench_transformer_flash_tpu.json (B64 S256), the benchmark's cell and
# hw_pr25/bench_attention_dispatch_tpu.jsonl (S384, S512).
_RECORDED = [
    ("B1-H8-S1024-dense-33MB", (1, 1024, 8, 64), True, "dense"),
    ("B1-H8-S2048-flash-134MB", (1, 2048, 8, 64), True, "flash"),
    ("cell-B16-H8-S784-causal-flash-315MB", (16, 784, 8, 128), True, "flash"),
    ("cell-shape-non-causal-dense", (16, 784, 8, 128), False, "dense"),
    ("r3-trainer-B64-S256-dense-134MB", (64, 256, 8, 32), False, "dense"),
    ("B16-H8-S384-dense-75MB", (16, 384, 8, 128), True, "dense"),
    ("B8-H8-S512-flash-67MB", (8, 512, 8, 128), True, "flash"),
    ("classifier-B16-S2048-flash", (16, 2048, 8, 128), False, "flash"),
    ("tier1-tiny-dense", (8, 784, 2, 16), True, "dense"),
    ("kimi-cell-B2-H32-S8192-keys192-values128-flash", (2, 8192, 32, (192, 128)), True, "flash"),
    ("qwen3-next-cell-B2-H16-S8192-d256-flash", (2, 8192, 16, 256), True, "flash"),
]


@pytest.mark.parametrize("shape,causal,impl", [c[1:] for c in _RECORDED],
                         ids=[c[0] for c in _RECORDED])
def test_dispatch_predicate_on_recorded_shapes(shape, causal, impl):
    b, s, h, d = shape
    dk, dv = d if isinstance(d, tuple) else (d, d)      # (key, value) widths, or one
    plan = pa.dispatch_plan((b, s, h, dk), causal=causal,
                            **({"value_dim": dv} if dv != dk else {}))
    assert plan["impl"] == impl
    assert plan["score_bytes"] == 4 * b * h * s * s
    assert (plan["key_dim"], plan["value_dim"]) == (dk, dv)
    if impl == "dense":
        assert plan["seq_padded"] is plan["block"] is None
    else:
        assert plan["seq_padded"] % 128 == 0 and 0 <= plan["seq_padded"] - s < 128
        assert plan["seq_padded"] % plan["block"] == 0


# The plan's ``backward``: "fused" where one (batch, head)'s float32 dq, padded S x key
# width x 4 bytes, fits the budget the fused kernel keeps resident (every cell), "split"
# past it, None for a dense plan.
_BACKWARD = [
    ("kanana2-and-kimi-S8192-keys192", (2, 8192, 32, 192), 128, "fused"),
    ("lfm2-S8192-d64", (4, 8192, 32, 64), None, "fused"),
    ("qwen3-next-S8192-d256", (2, 8192, 16, 256), None, "fused"),
    ("nemotron-S8192-4-heads", (2, 8192, 4, 128), None, "fused"),
    ("lm-b16-S784-padded-896", (16, 784, 8, 128), None, "fused"),
    ("evabyte-windows-of-2048", (16, 2048, 16, 128), None, "fused"),
    ("budget-edge-S32768-d128", (1, 32768, 8, 128), None, "fused"),
    ("past-the-budget-S32768-keys192", (1, 32768, 8, 192), 128, "split"),
    ("past-the-budget-S65536-d128", (1, 65536, 8, 128), None, "split"),
    ("tier1-tiny-dense", (8, 784, 2, 16), None, None),
]


@pytest.mark.parametrize("shape,value_dim,backward", [c[1:] for c in _BACKWARD],
                         ids=[c[0] for c in _BACKWARD])
def test_dispatch_plan_names_the_backward(shape, value_dim, backward):
    assert pa.FUSED_DQ_MAX_BYTES == 4 * 32768 * 128 == 16 << 20
    plan = pa.dispatch_plan(shape, causal=True, value_dim=value_dim)
    assert plan["backward"] == backward
    if backward is not None:
        assert (4 * plan["seq_padded"] * shape[-1] <= pa.FUSED_DQ_MAX_BYTES) == (
            backward == "fused") == pa.backward_fused(plan["seq_padded"], shape[-1])


@pytest.mark.parametrize("widths", [32, (192, 128)], ids=["d32", "keys192-values128"])
def test_dispatch_plan_is_what_the_dispatcher_runs(monkeypatch, widths):
    """The plan's block and padded length are the ones ``flash_attention`` is
    called into, and cross-attention (S_q != S_k) stays dense."""
    monkeypatch.setattr(pa, "FLASH_MIN_SCORE_BYTES", 1)
    monkeypatch.setattr(pa, "FLASH_MIN_HEAD_SCORE_BYTES", 1)
    q, k, v = _qkv(200, seed=5, d=widths)
    plan = pa.dispatch_plan(q.shape, causal=True, value_dim=v.shape[-1])
    assert (plan["impl"], plan["seq_padded"]) == ("flash", 256)
    assert (plan["key_dim"], plan["value_dim"]) == (q.shape[-1], v.shape[-1])
    np.testing.assert_array_equal(
        np.asarray(pa.dispatch_attention(q, k, v, causal=True)),
        np.asarray(pa.flash_attention(q, k, v, causal=True, block=plan["block"])))
    assert pa.dispatch_plan(q.shape, causal=True, k_len=100)["impl"] == "dense"
    np.testing.assert_array_equal(
        np.asarray(pa.dispatch_attention(q, k[:, :100], v[:, :100])),
        np.asarray(full_attention(q, k[:, :100], v[:, :100])))


def test_the_environment_does_not_choose_the_program(monkeypatch):
    """Two variables once switched the kernels to other operand layouts at trace
    time, so a shell that had them set timed another program. Nothing reads them:
    the plan at both cells' shapes and the traced program are what they are without
    them."""
    cells = [(16, 784, 8, 128), (4, 8192, 32, 64)]
    q, k, v = _qkv(256, d=128)

    def program():
        pa._make_op.cache_clear()       # a cached op would hide a read at trace time
        return ([pa.dispatch_plan(shape, causal=True) for shape in cells],
                str(jax.make_jaxpr(functools.partial(pa.flash_attention, causal=True))(
                    q, k, v)))

    plain = program()
    assert [(p["impl"], p["seq_padded"], p["block"]) for p in plain[0]] == [
        ("flash", 896, 896), ("flash", 8192, 1024)]
    assert "flash_fwd" in plain[1]
    monkeypatch.setenv("FLASH_NATIVE_LAYOUT", "1")
    monkeypatch.setenv("FLASH_NATIVE_MODE", "unroll")
    assert program() == plain


def _lm(attention_fn, **kw):
    from csed_514_project_distributed_training_using_pytorch_tpu.models import (
        lm as lm_mod,
    )
    return lm_mod.TransformerLM(vocab_size=17, seq_len=200, embed_dim=16, num_layers=1,
                                num_heads=4, num_kv_heads=2, rope=True,
                                attention_fn=attention_fn, **kw)


@pytest.mark.parametrize("window", [0, 64], ids=["full", "window64"])
def test_lm_loss_and_gradients_equal_dense_model(monkeypatch, window):
    """The model ``train/lm.py`` now builds (the dispatcher as its core) against the
    dense model, at a shape pushed over the threshold with a head dim of 4: the
    same loss and parameter gradients through GQA, RoPE and the padded kernels."""
    from csed_514_project_distributed_training_using_pytorch_tpu.models import (
        lm as lm_mod,
    )
    monkeypatch.setattr(pa, "FLASH_MIN_SCORE_BYTES", 4 * 2 * 4 * 200 * 200)
    monkeypatch.setattr(pa, "FLASH_MIN_HEAD_SCORE_BYTES", 4 * 200 * 200)
    dense = _lm(ops.full_attention, attention_window=window)
    routed = _lm(ops.dispatch_attention, attention_window=window)
    ids = jnp.asarray(np.random.default_rng(0).integers(0, 16, size=(2, 200)),
                      jnp.int32)
    assert pa.dispatch_plan((2, 200, 4, 4), causal=True, window=window)["impl"] == "flash"
    params = dense.init(jax.random.PRNGKey(0), ids)["params"]
    loss = lambda model: jax.value_and_grad(
        lambda p: lm_mod.next_token_loss(model, p, ids, jax.random.PRNGKey(1)))(params)
    (l_dense, g_dense), (l_routed, g_routed) = loss(dense), loss(routed)
    np.testing.assert_allclose(float(l_routed), float(l_dense), rtol=1e-6)
    flat_d, flat_r = (jax.tree_util.tree_leaves_with_path(g) for g in (g_dense, g_routed))
    for (path, a), (_, b) in zip(flat_d, flat_r):
        np.testing.assert_allclose(np.asarray(b), np.asarray(a), rtol=2e-4, atol=2e-6,
                                   err_msg=jax.tree_util.keystr(path))


def test_lm_window_guard_admits_the_dispatcher_only():
    model = _lm(functools.partial(full_attention), attention_window=8)
    with pytest.raises(ValueError, match="dense core and the dispatcher"):
        model.init(jax.random.PRNGKey(0), jnp.zeros((1, 200), jnp.int32))


def _compile_events(tmp_path, **config_kw):
    import json

    from csed_514_project_distributed_training_using_pytorch_tpu.data import mnist
    from csed_514_project_distributed_training_using_pytorch_tpu.train import lm as train_lm
    from csed_514_project_distributed_training_using_pytorch_tpu.utils.config import (
        LMConfig,
    )
    rng = np.random.default_rng(0)
    split = lambda n: mnist.Dataset(rng.random((n, 28, 28, 1), np.float32),
                                    np.zeros(n, np.int32), "test")
    tele = tmp_path / "t.jsonl"
    config = LMConfig(epochs=1, batch_size=8, eval_batch=8, embed_dim=16, num_layers=1,
                      num_heads=2, generate=0, results_dir="",
                      images_dir=str(tmp_path / "images"), telemetry=str(tele),
                      **config_kw)
    train_lm.main(config, datasets=(split(8), split(8)))
    with open(tele) as fh:
        events = [json.loads(line) for line in fh if line.strip()]
    return [e for e in events if e["event"] == "compile"]


@pytest.mark.parametrize("mesh", ["", "data=1"], ids=["all-devices", "one-device"])
def test_compile_event_reports_dense_for_a_tier1_sized_run(tmp_path, mesh):
    """A tier-1-sized run says ``dense`` whether the model keeps the dense core (a
    mesh of several devices) or the dispatcher chose it (one device)."""
    (event,) = _compile_events(tmp_path, mesh=mesh)
    assert event["attention"] == {"impl": "dense", "score_bytes": 4 * 8 * 2 * 784 * 784
                                  // (1 if mesh else jax.device_count()),
                                  "seq_padded": None, "block": None,
                                  "key_dim": 8, "value_dim": 8, "backward": None}


def test_compile_event_reports_flash_for_the_cells_shapes():
    """The cell's shapes through the same helper the trainer emits from: the
    predicate alone, no kernel run."""
    from csed_514_project_distributed_training_using_pytorch_tpu.train.lm import (
        _attention_plan,
    )
    from csed_514_project_distributed_training_using_pytorch_tpu.utils.config import (
        LMConfig,
    )
    from csed_514_project_distributed_training_using_pytorch_tpu.utils import (
        telemetry as T,
    )
    config = LMConfig(batch_size=16, embed_dim=1024, num_heads=8, kv_heads=2)
    plan = _attention_plan(config, 784, 1, (8, 128, None), dispatched=True)
    assert plan == pa.dispatch_plan((16, 784, 8, 128), causal=True)
    latent = _attention_plan(LMConfig(batch_size=2), 8192, 1, (32, 192, 128), dispatched=True)
    assert (latent["impl"], latent["key_dim"], latent["value_dim"], latent["seq_padded"]) == (
        "flash", 192, 128, 8192)
    assert (plan["impl"], plan["score_bytes"], plan["seq_padded"]) == (
        "flash", 314703872, 896)
    event = T.compile_event("epoch", {"lower_s": 1.0, "compile_s": 2.0},
                            steps_per_call=32, attention=plan)
    assert event["attention"]["impl"] == "flash"
    assert event["attention"]["block"] == plan["block"]
    assert event["attention"]["backward"] == latent["backward"] == "fused"
    kept = _attention_plan(config, 784, 4, (8, 128, None), dispatched=False)
    assert kept["impl"] == "dense" and kept["score_bytes"] == 314703872 // 4
    assert kept["backward"] is None
    assert kept["block"] is None
