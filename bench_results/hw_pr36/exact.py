"""Where the router's values and gradients differ between the parent's route (take_along_axis)
and this tree's (_pick), on the chip at nemotron_h_train_8k's shapes: the pick alone, then route
whole. usage: python bench_results/hw_pr36/exact.py   (chip only; _scratch/parent = the parent)"""
import importlib.util, os, sys
REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, REPO)
import jax, jax.numpy as jnp, numpy as np
from csed_514_project_distributed_training_using_pytorch_tpu.ops import moe
spec = importlib.util.spec_from_file_location("parent_moe", os.path.join(
    REPO, "_scratch/parent/csed_514_project_distributed_training_using_pytorch_tpu/ops/moe.py"))
parent = importlib.util.module_from_spec(spec); spec.loader.exec_module(parent)
print("device:", jax.devices()[0].device_kind)
t, k, router, d = (16384, 22, 512, 4096) if jax.default_backend() == "tpu" else (256, 6, 16, 64)
ks = jax.random.split(jax.random.PRNGKey(36), 5)
u = jax.random.normal(ks[0], (t, d), jnp.bfloat16)
kernel = 0.02 * jax.random.normal(ks[1], (d, router), jnp.float32)
bias = jnp.zeros((router,), jnp.float32)
w = jax.random.normal(ks[2], (t, k), jnp.float32)
scores = jax.nn.sigmoid(jax.random.normal(ks[3], (t, router), jnp.float32))
experts = jax.lax.top_k(scores, k)[1].astype(jnp.int32)
gap = lambda a, b: (float(jnp.abs(a.astype(jnp.float32) - b.astype(jnp.float32)).max()),
                    float(jnp.linalg.norm((a.astype(jnp.float32) - b.astype(jnp.float32)).ravel())
                          / (jnp.linalg.norm(b.astype(jnp.float32).ravel()) + 1e-30)))
old = jax.jit(lambda s: jax.value_and_grad(lambda s: jnp.sum(w * jnp.take_along_axis(s, experts, axis=-1)))(s))(scores)
new = jax.jit(lambda s: jax.value_and_grad(lambda s: jnp.sum(w * moe._pick(s, experts)))(s))(scores)
print("pick alone: picked equal", bool((jax.jit(moe._pick)(scores, experts) == jnp.take_along_axis(scores, experts, axis=-1)).all()),
      "gradient (largest |difference|, relative norm):", gap(new[1], old[1]))
def both(route):
    def f(u, kernel):
        (loss, (weights, chosen)), grads = jax.value_and_grad(
            lambda u, kernel: (lambda r: (jnp.sum(w * r[0]), r))(route(u, kernel, bias, top_k=k, scaling=5.0)),
            argnums=(0, 1), has_aux=True)(u, kernel)
        return weights, chosen, grads
    return jax.jit(f)(u, kernel)
(w0, e0, g0), (w1, e1, g1) = both(parent.route), both(moe.route)
print("route: experts that differ", int((e0 != e1).sum()), "of", e0.size, "weights", gap(w1, w0),
      "du", gap(g1[0], g0[0]), "dkernel", gap(g1[1], g0[1]), "|dkernel| largest", float(jnp.abs(g0[1]).max()))
