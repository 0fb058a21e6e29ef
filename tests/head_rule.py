"""What ``tests/test_hybrid_lm.py`` (a tied table ``[vocab, d]``) and
``tests/test_nemotron_h.py`` (an untied ``[d, vocab]``) both hold of
``hybrid_lm.head_nll``, the head's loss with a differentiation rule of its own: the
checks, written once, for the cases each file parametrises."""

import jax
import jax.numpy as jnp
import numpy as np

from csed_514_project_distributed_training_using_pytorch_tpu.models import hybrid_lm

DTYPES = {"float32": jnp.float32, "bf16": jnp.bfloat16}
# value and gradient: float32 to a rounding of the sum, bf16 as
# test_loss_and_gradients_match_the_reference holds a gradient (a share of the leaf's
# largest entry); a scaled gradient is rounded once more, to the dtype's eight bits
CLOSE = {"float32": 1e-6, "bf16": 2e-4}
SCALED = {"float32": 1e-6, "bf16": 2.0 ** -7}


def head_inputs(model, params, ids):
    """``(table, hidden, targets)`` as ``HybridLM.nll`` hands them to the rule."""
    hidden, _ = model.hidden_states(params, ids)
    return (model._head(params), hidden,
            jnp.roll(ids.astype(jnp.int32), -1, axis=1)[..., None])


def _value_and_grads(fn, model, inputs, scale=1.0):
    """``(scale · fn, its gradients with respect to table and hidden states)``; ``fn``
    the rule, or the plain formula it wraps (``hybrid_lm._summed_nll``) left to
    autodiff, which is what the rule is held to."""
    table, hidden, targets = inputs
    return jax.value_and_grad(lambda t, h: scale * fn(model, t, h, targets),
                              argnums=(0, 1))(table, hidden)


def _assert_close(got, want, share, what):
    got, want = (np.asarray(x, np.float32) for x in (got, want))
    assert got.shape == want.shape, what
    assert np.abs(got - want).max() <= share * (np.abs(want).max() + 1e-30), what


def check_value_and_gradients(model, params, ids, dtype: str):
    """The rule's loss is the plain formula's to the float, asked for a gradient or
    not; its gradients with respect to table and hidden states are ``jax.grad``'s of
    that formula, in their dtypes."""
    inputs = head_inputs(model, params, ids)
    loss, grads = _value_and_grads(hybrid_lm.head_nll, model, inputs)
    want, want_grads = _value_and_grads(hybrid_lm._summed_nll, model, inputs)
    np.testing.assert_array_equal(loss, want)
    np.testing.assert_array_equal(hybrid_lm.head_nll(model, *inputs), want)
    for g, w, what in zip(grads, want_grads, ("table", "hidden")):
        assert g.dtype == w.dtype, what
        _assert_close(g, w, CLOSE[dtype], what)


def check_a_cotangent_scales_both_gradients(model, params, ids, dtype: str, scale: float):
    """A loss that is ``scale`` times the sum hands the rule's backward pass ``scale``
    as its cotangent: both gradients are ``scale`` times the plain formula's."""
    inputs = head_inputs(model, params, ids)
    loss, grads = _value_and_grads(hybrid_lm.head_nll, model, inputs, scale)
    total, unscaled = _value_and_grads(hybrid_lm._summed_nll, model, inputs)
    np.testing.assert_allclose(loss, scale * total, rtol=1e-6)
    for g, w, what in zip(grads, unscaled, ("table", "hidden")):
        _assert_close(g, scale * np.asarray(w, np.float32), SCALED[dtype], what)


def check_the_last_row_gets_no_gradient(model, params, ids):
    """A sequence's last row has no target: its hidden state's gradient is zero, and
    every other row's is not."""
    _, (_, d_hidden) = _value_and_grads(hybrid_lm.head_nll, model,
                                        head_inputs(model, params, ids))
    rows = np.abs(np.asarray(d_hidden, np.float32)).max(axis=-1)
    assert (rows[:, -1] == 0).all() and (rows[:, :-1] > 0).all()


PRODUCT_CASES = {"value and gradient": 3, "value and gradient, remat": 3,
                 "the loss alone": 1, "the inner function under jax.checkpoint": 4}


def check_head_products(build, ids, case: str, monkeypatch):
    """How many products touch the ``[T, vocab]`` logits (``HybridLM.head_products``)
    in a program's jaxpr: three where the loss is differentiated, with ``remat`` and
    without; one where nothing asks a gradient (``make_eval_nll_fn``'s call); four with
    the rule's inner function under a ``jax.checkpoint``, as the head was before the
    rule. ``build(remat=...)`` gives ``(model, params)``."""
    model, params = build(remat=case.endswith("remat"))
    if "jax.checkpoint" in case:
        monkeypatch.setattr(hybrid_lm, "_summed_nll", jax.checkpoint(
            hybrid_lm._summed_nll, static_argnums=(0,)))
    program = model.nll if case == "the loss alone" else \
        jax.value_and_grad(model.loss, has_aux=True)
    assert model.head_products(jax.make_jaxpr(program)(params, ids), ids.size) \
        == PRODUCT_CASES[case]
