"""A norm of a float32 residual stream under matmuls of a narrower dtype
(``HybridLM.normed``): ``ops.rms_norm`` and the cast, the output held behind an optimization
barrier whose transpose holds the cotangent; any other stream is left to the compiler."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from csed_514_project_distributed_training_using_pytorch_tpu import ops
from csed_514_project_distributed_training_using_pytorch_tpu.models import hybrid_lm

BF = jnp.bfloat16


def _model(hidden, dtype, fp32_residual, offset=True):
    return hybrid_lm.HybridLM(
        vocab_size=64, seq_len=32, hidden_size=hidden, intermediate_size=2 * hidden,
        moe_intermediate_size=hidden, num_attention_heads=2, num_key_value_heads=2,
        layer_types=("conv",), num_dense_layers=1, router_experts=1, held_experts=(0, 1),
        num_experts_per_tok=1, dtype=dtype, fp32_residual=fp32_residual,
        norm_unit_offset=offset)


def _barriers(fn, *args) -> int:
    return str(jax.make_jaxpr(fn)(*args)).count("optimization_barrier")


@pytest.mark.parametrize("offset", [False, True], ids=["scale", "unit-offset"])
@pytest.mark.parametrize("d", [128, 512])
@pytest.mark.parametrize("rows", [256, 1024])
def test_a_held_norm_is_rms_norm_and_the_cast_value_and_every_gradient(rows, d, offset):
    """The barrier moves no bit: the output is ``ops.rms_norm`` cast to the model's dtype,
    and ``dx`` (float32) and the weight's gradient (float32) are ``jax.vjp``'s of that
    composition."""
    model = _model(d, BF, True, offset)
    k = jax.random.split(jax.random.PRNGKey(rows + d), 3)
    x = 3.0 * jax.random.normal(k[0], (2, rows // 2, d), jnp.float32)
    leaf = "norm_offset" if offset else "norm_scale"
    p = {leaf: 0.1 * jax.random.normal(k[1], (d,), jnp.float32) + (0.0 if offset else 1.0)}
    du = jax.random.normal(k[2], x.shape, jnp.float32).astype(BF)
    plain = lambda x, p: ops.rms_norm(x, p[leaf], eps=model.norm_eps,
                                      offset=1.0 if offset else 0.0).astype(BF)
    assert model.holds_norms and _barriers(model.normed, x, p) == 1
    (got, pull), (want, pull_plain) = jax.vjp(model.normed, x, p), jax.vjp(plain, x, p)
    assert got.dtype == BF
    np.testing.assert_array_equal(np.asarray(got, np.float32), np.asarray(want, np.float32))
    (dx, dp), (dx_plain, dp_plain) = pull(du), pull_plain(du)
    assert dx.dtype == jnp.float32 and dp[leaf].dtype == jnp.float32
    np.testing.assert_allclose(dx, dx_plain, rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(dp[leaf], dp_plain[leaf], rtol=1e-6, atol=1e-5)
    # the cotangent is held too: the barrier's transpose is a barrier
    assert _barriers(lambda x, p: jax.vjp(model.normed, x, p)[1](du), x, p) == 2


@pytest.mark.parametrize("dtype,fp32_residual,stream", [
    (jnp.float32, True, jnp.float32), (BF, False, BF), (jnp.float32, False, jnp.float32)],
    ids=["float32-model", "bf16-stream", "float32-throughout"])
def test_a_stream_in_the_models_dtype_is_left_to_the_compiler(dtype, fp32_residual, stream):
    model = _model(128, dtype, fp32_residual)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 32, 128), jnp.float32).astype(stream)
    p = {"norm_offset": 0.1 * jax.random.normal(jax.random.PRNGKey(2), (128,))}
    assert not model.holds_norms and _barriers(model.normed, x, p) == 0
    assert _barriers(jax.grad(lambda x: jnp.sum(model.normed(x, p).astype(jnp.float32))), x) == 0
    assert model.normed(x, p).dtype == dtype
    assert model.norm_plan() == {"impl": "xla", "calls": 3}
    held = dataclasses.replace(model, dtype=BF, fp32_residual=True)
    assert held.norm_plan() == {"impl": "barrier", "calls": 3}
