"""Kernels of the main path compiled, at their real widths, for the chip the
benchmark runs on: a described ``v5e``, not an attached one, so this costs no
chip time and needs none. Mosaic refuses here what it would refuse there (block
shapes against the tiling, fast memory a kernel may use); nothing runs, so
nothing is said about results or times.

All such tests live in this one file: the worker that gets it loads the TPU's
library once, inside the fixture, after collection.
"""

import contextlib
import dataclasses
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:      # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@contextlib.contextmanager
def lowering_for_the_chip(*modules):
    """Inside, the Pallas kernels of ``modules`` lower for Mosaic and not for the
    interpreter (``_interpret`` sees the CPU here), and nothing is written to a
    compile cache that could not be read back without a chip."""
    cache = jax.config.jax_enable_compilation_cache
    interprets = [module._interpret for module in modules]
    jax.config.update("jax_enable_compilation_cache", False)
    for module in modules:
        module._interpret = lambda: False
    try:
        yield
    finally:
        for module, interpret in zip(modules, interprets):
            module._interpret = interpret
        jax.config.update("jax_enable_compilation_cache", cache)


@contextlib.contextmanager
def mosaics_own_limit(kda):
    """Inside, the scan kernels of ``ops/kda.py`` ask for no scoped fast memory, which leaves
    them Mosaic's own 16 MiB (``_make_op`` caches the jitted halves, so the cache is cleared on
    the way in and out)."""
    params = kda._params
    kda._params = lambda: dataclasses.replace(params(), vmem_limit_bytes=None)
    kda._make_op.cache_clear()
    try:
        yield
    finally:
        kda._params = params
        kda._make_op.cache_clear()


KERNELS = ("moe_ffn_fwd", "moe_ffn_bwd", "moe_ffn_dw",
           "moe_pack", "moe_gather", "moe_combine")
D, F, ROUTER, HELD, K = 2048, 1536, 64, 8, 4        # LFM2-24B-A2B, chip 0 of 8


@pytest.fixture(scope="module")
def compiled_layer(one_chip):
    """``ops/moe.py`` (route and the held experts, value and every gradient) compiled
    at the published widths for ``tokens`` tokens: the program's text, once a size."""
    from csed_514_project_distributed_training_using_pytorch_tpu.ops import moe
    texts = {}

    def compile_for(tokens):
        if tokens in texts:
            return texts[tokens]
        spec = lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

        def layer(u, router_kernel, bias, w1, w3, w2):
            weights, experts = moe.route(u, router_kernel, bias, top_k=K)
            out, counts = moe.held_experts_ffn(u, weights, experts, w1, w3, w2,
                                               held=(0, HELD))
            return jnp.sum(out.astype(jnp.float32)), counts

        with lowering_for_the_chip(moe):
            texts[tokens] = jax.jit(jax.value_and_grad(
                layer, argnums=(0, 1, 3, 4, 5), has_aux=True)).lower(
                spec((tokens, D), jnp.bfloat16), spec((D, ROUTER), jnp.float32),
                spec((ROUTER,), jnp.float32), spec((D, HELD * F), jnp.float32),
                spec((D, HELD * F), jnp.float32), spec((F, HELD * D), jnp.float32)
            ).compile().as_text()
        return texts[tokens]

    return compile_for


@pytest.mark.parametrize("tokens", [4096, 32768])
def test_expert_layer_kernels_compile_for_the_v5e_at_published_widths(compiled_layer, tokens):
    """Forward and backward at LFM2-24B-A2B's widths (d 2048, expert width 1536, 8 of
    64 experts held, top-4), at 4,096 tokens and at the cell's 32,768: the three
    product kernels (the whole of one expert's weights resident in VMEM) and the
    three of the crossings are in the program."""
    from csed_514_project_distributed_training_using_pytorch_tpu.ops import moe
    text = compiled_layer(tokens)
    assert "tpu_custom_call" in text
    for name in KERNELS:
        assert f"%{name}" in text, name
    plan = moe.expert_plan(tokens, top_k=K, held=(0, HELD))
    assert plan["rows_buffer"] == tokens * K + HELD * moe.ROW_TILE
    assert plan["rows_moved"] == "arrived"


@pytest.mark.parametrize("tokens", [4096, 32768])
def test_no_crossing_is_left_to_xla_at_the_size_of_the_bound(compiled_layer, tokens):
    """Outside the Pallas calls nothing gathers, selects or fills ``rows_buffer`` (or
    ``k·T``) rows of width ``d``: the crossings move the row tiles that arrived."""
    import re
    from csed_514_project_distributed_training_using_pytorch_tpu.ops import moe
    plan = moe.expert_plan(tokens, top_k=K, held=(0, HELD))
    sized = re.compile(rf"\[({plan['rows_buffer']}|{plan['row_bound']}),{D}\]")
    offenders = [line.strip()[:200] for line in compiled_layer(tokens).splitlines()
                 if " = " in line and sized.search(line.split(" = ", 1)[1].split("(")[0])
                 and "tpu_custom_call" not in line
                 and not re.search(r"= \S+ (parameter|get-tuple-element|bitcast)\(", line)]
    assert not offenders, offenders


def test_the_lfm2_step_keeps_its_flash_forward_and_fits_the_v5e(one_chip):
    """The training step of ``lfm2-24b-a2b-ep8`` at the cell's batch, 4 x 8192 tokens
    (value, gradient, clip, AdamW; bf16, per-block recomputation on), compiled for the
    described chip: what recomputation keeps leaves arguments + temporaries under
    15.0 GB of the chip's 15.75, and the differentiated step calls ``flash_fwd``
    once for its one attention layer, not again in the backward pass, which is the one
    fused kernel (``flash_dkv``; no ``flash_dq``)."""
    import re
    from csed_514_project_distributed_training_using_pytorch_tpu import ops
    from csed_514_project_distributed_training_using_pytorch_tpu.models import hybrid_lm
    from csed_514_project_distributed_training_using_pytorch_tpu.ops import (
        moe, optim, pallas_attention)
    from csed_514_project_distributed_training_using_pytorch_tpu.train.step import (
        create_train_state, make_train_step)
    config = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                          "benchmark", "configs", "lfm2-24b-a2b-ep8.json")
    batch, seq = 4, 8192
    model = hybrid_lm.from_config_file(
        config, vocab_size=8192, seq_len=seq, dtype=jnp.bfloat16, remat=True,
        attention_fn=ops.dispatch_attention)
    assert model.layer_types.count("full_attention") == 1
    optimizer = optim.freeze(optim.make_optimizer(
        "adamw", learning_rate=1e-6, momentum=0.0, weight_decay=0.01), hybrid_lm.is_frozen)
    step = make_train_step(
        model, learning_rate=1e-6, momentum=0.0, optimizer=optimizer, clip_grad_norm=1.0,
        loss_fn=lambda params, xs, ys, rng: model.loss(params, xs), loss_has_aux=True)
    on_chip = lambda tree: jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip), tree)
    state = jax.eval_shape(lambda: create_train_state(
        model, jax.random.PRNGKey(0), sample_input_shape=(1, seq), optimizer=optimizer))
    tokens = jax.ShapeDtypeStruct((batch, seq), jnp.int32)
    labels = jax.ShapeDtypeStruct((batch,), jnp.int32)
    rng = jax.eval_shape(lambda: jax.random.PRNGKey(0))

    with lowering_for_the_chip(moe, pallas_attention):
        compiled = jax.jit(step, donate_argnums=(0,)).lower(
            *on_chip((state, tokens, labels, rng))).compile()
    memory = compiled.memory_analysis()
    assert memory.argument_size_in_bytes + memory.temp_size_in_bytes <= 15.0e9
    calls = lambda kernel: len(re.findall(rf"%{kernel}[.\d]* = ", compiled.as_text()))
    assert (calls("flash_fwd"), calls("flash_dq"), calls("flash_dkv")) == (1, 0, 1)


# NVIDIA-Nemotron-3-Super-120B-A12B, chip 0 of 64: 16 Mamba-2 heads of 64 in one group with
# a state of 128; 8 of 512 relu² experts of 1024 x 2688, 22 a token; 2 x 8192 tokens
SSM = dict(batch=2, seq=8192, heads=16, head_dim=64, groups=1, state=128)
LATENT, EXPERT_F, ROUTER_512, K_22, TOKENS = 1024, 2688, 512, 22, 2 * 8192


# the ``falcon_h1`` cell's: one sequence, a head's state 128 x 256, four times the above
SSM_WIDE = dict(batch=1, seq=8192, heads=8, head_dim=128, groups=1, state=256)


@pytest.mark.parametrize("sizes", [SSM, SSM_WIDE], ids=["P64-N128-H16", "P128-N256-H8"])
def test_scan_kernels_compile_for_the_v5e_at_published_widths(one_chip, sizes):
    """``ssd_fwd`` and ``ssd_bwd`` at each cell's shapes (chunk 128; P 64, N 128, sixteen
    heads a grid step; P 128, N 256, eight): Mosaic takes the per-head column slices, the
    transposed-operand products and the carried state, and the scoped fast memory either
    size asks for is under its limit: an overflow fails here and not on the chip."""
    from csed_514_project_distributed_training_using_pytorch_tpu.ops import ssm
    b, s, h, p, g, n = (sizes[k] for k in ("batch", "seq", "heads", "head_dim", "groups", "state"))
    spec = lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    x, step = spec((b, s, h, p), jnp.bfloat16), spec((b, s, h), jnp.float32)
    bc = spec((b, s, g, n), jnp.bfloat16)
    loss = lambda *args: jnp.sum(ssm.ssd_scan(*args).astype(jnp.float32))
    with lowering_for_the_chip(ssm):
        text = jax.jit(jax.grad(loss, argnums=(0, 1, 2, 3, 4))).lower(
            x, step, step, bc, bc).compile().as_text()
    assert "%ssd_fwd" in text and "%ssd_bwd" in text


def test_latent_expert_layer_compiles_for_the_v5e_within_the_true_bound(one_chip):
    """The relu² two-matrix product on latent rows, 22 assignments a token over 512
    experts with 8 held: the expert-order buffers are ``min(k, held) · T`` rows and a
    tile an expert, and no array of ``k · T`` rows of the latent width is in the
    program."""
    import re
    from csed_514_project_distributed_training_using_pytorch_tpu.ops import moe
    spec = lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def layer(l, u, router_kernel, bias, w1, w2):
        weights, experts = moe.route(u, router_kernel, bias, top_k=K_22, scaling=5.0,
                                     eps=1e-20)
        out, counts = moe.held_experts_ffn(l, weights, experts, w1, None, w2,
                                           held=(0, HELD))
        return jnp.sum(out.astype(jnp.float32)), counts

    with lowering_for_the_chip(moe):
        text = jax.jit(jax.value_and_grad(layer, argnums=(0, 2, 4, 5), has_aux=True)).lower(
            spec((TOKENS, LATENT), jnp.bfloat16), spec((TOKENS, 4096), jnp.bfloat16),
            spec((4096, ROUTER_512), jnp.float32), spec((ROUTER_512,), jnp.float32),
            spec((LATENT, HELD * EXPERT_F), jnp.float32),
            spec((EXPERT_F, HELD * LATENT), jnp.float32)).compile().as_text()
    for name in KERNELS:
        assert f"%{name}" in text, name
    plan = moe.expert_plan(TOKENS, top_k=K_22, held=(0, HELD))
    assert plan["row_bound"] == HELD * TOKENS
    assert plan["rows_buffer"] == HELD * TOKENS + HELD * moe.ROW_TILE
    assert f"[{plan['rows_buffer']},{LATENT}]" in text
    assert not re.search(rf"\[{K_22 * TOKENS}(,\d+)*,{LATENT}\]", text)


# Kimi-Linear-48B-A3B, chip 0 of 32: 32 KDA heads of 128 x 128; 32 attention heads whose keys
# are 192 wide (128 from the latent, 64 shared) and whose values are 128; 2 x 8192 tokens
KDA = dict(batch=2, seq=8192, heads=32, head_dim=128)


def test_delta_rule_kernels_compile_for_the_v5e_at_published_widths(one_chip):
    """``kda_fwd`` and ``kda_bwd`` at the cell's shapes, on the flat layout at the per-channel
    branch's committed tiling (``kda.KDA_TILING``: chunks of 128 in sub-blocks of 4, four chunks
    a grid step side by side before their states, a 128 x 128 state): Mosaic takes the sublane rolls of the exact diagonals, the far
    pairs' product a doubling of the block, the four triangular inverses' products, the lane
    select of a head's β out of the ``[512, 32]`` block, the norms' lane reductions, the
    row that ``dβ`` leaves as, and the transpose of the whole group that ``jax.vjp`` traces
    into the backward kernel, with the scoped fast memory ``kda._params`` asks for
    (``kda.VMEM_LIMIT``) and not without it: ``kda_bwd`` holds 17.15 MiB, over Mosaic's own 16."""
    from csed_514_project_distributed_training_using_pytorch_tpu.ops import kda
    b, s, h, d = (KDA[k] for k in ("batch", "seq", "heads", "head_dim"))
    spec = lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    x, g, beta = (spec((b, s, h * d), jnp.bfloat16), spec((b, s, h * d), jnp.float32),
                  spec((b, s, h), jnp.float32))
    loss = lambda *args: jnp.sum(kda.kda_scan(*args, eps=1e-5).astype(jnp.float32))
    compile_pair = lambda: jax.jit(jax.grad(loss, argnums=(0, 1, 2, 3, 4))).lower(
        x, x, x, g, beta).compile()
    assert kda._params().vmem_limit_bytes == kda.VMEM_LIMIT
    with lowering_for_the_chip(kda):
        compiled = compile_pair()
        with mosaics_own_limit(kda), pytest.raises(Exception, match="exceeded scoped vmem limit"):
            compile_pair()
    text = compiled.as_text()
    assert "%kda_fwd" in text and "%kda_bwd" in text
    chunk, sub, group = kda.KDA_TILING
    plan = kda.scan_plan(heads=h, key_dim=d, value_dim=d, seq_len=s)
    assert (plan["chunk"], plan["sub_block"], plan["group"]) == (chunk, sub, group)
    rows = group * chunk
    assert plan["states_per_sequence"] == s // rows
    kept = f"f32[{b},{s // rows},{h},{d},{d}]"      # a state a group
    assert kept in text and f"f32[{b},{s // chunk},{h},{d},{d}]" not in text
    assert f"f32[{b},{h},{s // rows},1,{rows}]" in text         # dβ, a row a program
    assert [x.shape for x in jax.tree.leaves(compiled.out_info)] == \
        [(b, s, h * d)] * 4 + [(b, s, h)]


def test_a_delta_rule_layer_never_leaves_the_flat_layout(one_chip):
    """``kda_mixer``, value and every gradient, at the cell's shapes: a head's channels
    stay 128 lanes of ``[2, 8192, 4096]`` from the projections to the output projection.
    No array of the program, fused or not and in any dtype, has 32 heads on the sublanes
    (trailing dimensions ``32, 128`` of B·S·4096 elements: ``[2, 8192, 32, 128]``, ``[2048, 8,
    32, 128]``), and no per-head factor is broadcast and rewritten flat (a ``reshape`` of a
    ``broadcast`` to ``[2, 8192, 4096]``). With the norms, β and the output's statistic
    outside the kernels (PR 32) this count found 65 and 7."""
    import math
    import re
    from csed_514_project_distributed_training_using_pytorch_tpu.models import hybrid_lm
    from csed_514_project_distributed_training_using_pytorch_tpu.ops import kda
    b, s, h, d = (KDA[k] for k in ("batch", "seq", "heads", "head_dim"))
    config = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                          "benchmark", "configs", "kimi-linear-48b-a3b-ep32.json")
    model = hybrid_lm.from_config_file(config, vocab_size=20480, seq_len=s,
                                       dtype=jnp.bfloat16, remat=True)
    assert (model.kda_heads, model.kda_head_dim) == (h, d)
    # the record says what the kernels ran: the ``compile`` event's ``kda`` field is this plan
    # and the tiling is the kernels' own for this stack's decay kind, which the file does not name
    plan = model.kda_plan()
    assert model.kda_tiling is None
    assert (plan["chunk"], plan["sub_block"], plan["group"]) == kda.KDA_TILING
    on_chip = lambda tree: jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip), tree)
    p = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0)))["params"]["layer_0"]["kda"]
    u = jax.ShapeDtypeStruct((b, s, model.hidden_size), jnp.bfloat16)
    loss = lambda p, u: jnp.sum(hybrid_lm.kda_mixer(p, u, model).astype(jnp.float32))
    with lowering_for_the_chip(kda):
        text = jax.jit(jax.grad(loss, argnums=(0, 1))).lower(*on_chip((p, u))).compile().as_text()
    assert "%kda_fwd" in text and "%kda_bwd" in text
    by_head = [m.group(0) for m in re.finditer(rf"= \w+\[([\d,]+),{h},{d}\]", text)
               if math.prod(map(int, m.group(1).split(","))) == b * s]
    assert not by_head, by_head
    rewritten = re.findall(rf"= \w+\[{b},{s},{h * d}\]\S* reshape\(\S*broadcast\S*", text)
    assert not rewritten, rewritten


def test_flash_kernels_compile_for_the_v5e_at_latent_attentions_widths(one_chip):
    """``flash_fwd`` and the fused backward (``flash_dkv``, 6.3 MB of float32 dq resident a
    (batch, head); no ``flash_dq``) with keys of 192 channels and values of 128 (a lane
    register and a half against one): the output and ``dv`` are 128 wide, ``dq`` and
    ``dk`` 192, and nothing is padded to a common width."""
    from csed_514_project_distributed_training_using_pytorch_tpu.ops import pallas_attention
    b, s, h = KDA["batch"], KDA["seq"], KDA["heads"]
    spec = lambda width: jax.ShapeDtypeStruct((b, s, h, width), jnp.bfloat16,
                                              sharding=one_chip)
    loss = lambda q, k, v: jnp.sum(pallas_attention.flash_attention(
        q, k, v, causal=True).astype(jnp.float32))
    with lowering_for_the_chip(pallas_attention):
        compiled = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
            spec(192), spec(192), spec(128)).compile()
    text = compiled.as_text()
    assert pallas_attention.backward_fused(s, 192)
    assert "%flash_fwd" in text and "%flash_dkv" in text and "%flash_dq" not in text
    widths = [x.shape[-1] for x in jax.tree.leaves(compiled.out_info)]
    assert widths == [192, 192, 128]       # dq, dk, dv
    assert f"bf16[{b * h},{s},256]" not in text


@pytest.mark.parametrize("s,d,backward", [(32768, 128, "fused"), (32768, 192, "split")],
                         ids=["budget-edge-fused", "past-the-budget-split"])
def test_flash_backward_compiles_for_the_v5e_on_both_sides_of_the_budget(one_chip, s, d,
                                                                          backward):
    """The resident dq's budget, S x D x 4 <= 16 MiB, at its edge (the fused kernel holds
    16 MiB of float32 dq, its bf16 output block twice and a block pair's tiles under the
    100 MiB it asks for) and just past it (``flash_dq`` and ``flash_dkv`` apart)."""
    from csed_514_project_distributed_training_using_pytorch_tpu.ops import pallas_attention
    assert pallas_attention.backward_fused(s, d) == (backward == "fused")
    block = pallas_attention.auto_block(s)
    spec = lambda shape, dtype=jnp.bfloat16: jax.ShapeDtypeStruct(shape, dtype,
                                                                  sharding=one_chip)
    stat = spec((2, s // block, 1, block), jnp.float32)
    with lowering_for_the_chip(pallas_attention):
        text = jax.jit(lambda *a: pallas_attention.flash_backward_blocks(
            *a, causal=True, block=block)).lower(
            spec((2, s, d)), spec((2, s, d)), spec((2, s, 128)), spec((2, s, 128)),
            stat, stat).compile().as_text()
    assert "%flash_dkv" in text
    assert ("%flash_dq" in text) == (backward == "split")


def test_eva_attention_kernels_compile_for_the_v5e_at_published_widths(one_chip):
    """One sequence of 32768 bytes, the 16 held heads of 128, windows of 2048 and chunks of
    16: the local half's ``flash_fwd`` and fused ``flash_dkv`` over ``[256, 2048, 128]`` and
    the remote half's ``eva_fwd``, ``eva_dq``, ``eva_dkv`` over 2048 summaries, value and
    every gradient."""
    from csed_514_project_distributed_training_using_pytorch_tpu.ops import (
        eva, pallas_attention,
    )
    heads, s, d, window, chunk = 16, 32768, 128, 2048, 16
    spec = lambda rows: jax.ShapeDtypeStruct((heads, rows, d), jnp.bfloat16,
                                             sharding=one_chip)
    loss = lambda *operands: jnp.sum(eva.kernel_attention(
        *operands, window=window, chunk=chunk).astype(jnp.float32))
    with lowering_for_the_chip(eva, pallas_attention):
        compiled = jax.jit(jax.grad(loss, argnums=(0, 1, 2, 3, 4))).lower(
            spec(s), spec(s), spec(s), spec(s // chunk), spec(s // chunk)).compile()
    text = compiled.as_text()
    for name in ("flash_fwd", "flash_dkv", "eva_fwd", "eva_dq", "eva_dkv"):
        assert f"%{name}" in text, name
    assert "%flash_dq" not in text
    assert [x.shape for x in jax.tree.leaves(compiled.out_info)] == \
        [(heads, s, d)] * 3 + [(heads, s // chunk, d)] * 2


def test_a_latent_attention_layer_rotates_the_shared_key_once(one_chip):
    """``mix`` of an ``mla`` layer of the ``deepseek_v3`` file, value and every gradient, at
    the cell's shapes (2 x 8192 tokens, 32 heads of 128 + 64 / 128): the flash kernels take
    keys of 192 channels, and in the forward pass the rotation's product (``mla_attention/
    rotary``, a signed permutation of the 64 shared channels) runs twice: over the 32 heads'
    queries, whole and in place since PR 48 (``[2, 8192, 32, 192]``, the permutation's other
    128 columns zero), and over the one shared key ``[2, 8192, 64]``, before it is handed to
    the heads, not over 32 copies of it."""
    import math
    import re
    from csed_514_project_distributed_training_using_pytorch_tpu import ops
    from csed_514_project_distributed_training_using_pytorch_tpu.models import hybrid_lm
    from csed_514_project_distributed_training_using_pytorch_tpu.ops import pallas_attention
    b, s, h = KDA["batch"], KDA["seq"], KDA["heads"]
    config = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                          "benchmark", "configs", "kanana-2-30b-a3b-ep8.json")
    model = hybrid_lm.from_config_file(config, vocab_size=16032, seq_len=s, dtype=jnp.bfloat16,
                                       remat=True, attention_fn=ops.dispatch_attention)
    assert (model.num_attention_heads, model.head_dim, model.value_head_dim,
            model.qk_rope_head_dim) == (h, 192, 128, 64)
    on_chip = lambda tree: jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip), tree)
    p = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0)))["params"]["layer_0"]
    x = jax.ShapeDtypeStruct((b, s, model.hidden_size), jnp.bfloat16)
    loss = lambda p, x: jnp.sum(
        hybrid_lm.mix(p, x, jnp.arange(s), "mla", model).astype(jnp.float32))
    with lowering_for_the_chip(pallas_attention):
        text = jax.jit(jax.grad(loss, argnums=(0, 1))).lower(*on_chip((p, x))).compile().as_text()
    assert "%flash_fwd" in text and "%flash_dkv" in text and "%flash_dq" not in text
    forward = re.findall(r"^\s*(?:ROOT )?%\S+ = \w+\[([\d,]+)\]\S* .*"
                         r'op_name="[^"]*/jvp\(mla_attention\)/rotary/dot_general"',
                         text, flags=re.M)
    # a fusion and the product inside it both carry the name, at the key's own shape and at
    # the queries' whole heads, and at no other: none over the key handed to 32 heads
    elements = [math.prod(map(int, shape.split(","))) for shape in forward]
    assert sorted(set(elements)) == [b * s * 64, b * s * h * 192], forward


def test_an_evabyte_blocks_norm_backward_is_in_no_products_epilogue(one_chip):
    """One block of the benchmark's file at published widths (32768 bytes, 16 heads of 128,
    5504 feed-forward columns), bfloat16 under a float32 stream, under ``jax.checkpoint``:
    every norm's output stands behind a barrier, so no instruction that holds a product of
    the backward pass also yields a norm's weight gradient ``f32[4096]`` (the parent's
    ``fusion.2053``: the feed-forward norm's two reductions in the epilogue of the
    ``[32768, 5504] x [5504, 4096]`` product, 14.4 ms on the chip for 8.2)."""
    import re
    from csed_514_project_distributed_training_using_pytorch_tpu import ops
    from csed_514_project_distributed_training_using_pytorch_tpu.models import hybrid_lm
    from csed_514_project_distributed_training_using_pytorch_tpu.ops import (
        eva, pallas_attention,
    )
    s = 32768
    config = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                          "benchmark", "configs", "evabyte-6.5b-tp2.json")
    model = hybrid_lm.from_config_file(config, vocab_size=320, seq_len=s, dtype=jnp.bfloat16,
                                       remat=True, attention_fn=ops.dispatch_attention)
    assert model.norm_plan() == {"impl": "barrier", "calls": 13}
    block = jax.checkpoint(
        hybrid_lm.make_block(model, "eva", False),
        policy=jax.checkpoint_policies.save_only_these_names(*model.kept))
    on_chip = lambda tree: jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip), tree)
    p = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0)))["params"]["layer_0"]
    x = jax.ShapeDtypeStruct((1, s, model.hidden_size), jnp.float32)
    loss = lambda p, x: jnp.sum(block(p, x, jnp.arange(s))[0])
    with lowering_for_the_chip(eva, pallas_attention):
        text = jax.jit(jax.grad(loss, argnums=(0, 1))).lower(*on_chip((p, x))).compile().as_text()
    # an instruction's result (a fusion's may be a tuple) where its op_name is a product's
    products = re.findall(r"^\s*(?:ROOT )?%\S+ = (.+?) (?:fusion|convolution)\(.*"
                          r'op_name="[^"]*transpose\(jvp[^"]*/dot_general"', text, flags=re.M)
    assert len(products) >= 10, "the backward pass's products carry their op_name"
    assert not [result for result in products if "f32[4096]" in result], products


# Qwen3-Next-80B-A3B, chip 0 of 16: 32 delta value heads on 16 key heads of 128 x 128, one decay
# a token and head; 16 attention heads of 256 on 2 key/value heads; 2 x 8192 tokens
GDN = dict(batch=2, seq=8192, key_heads=16, heads=32, head_dim=128, attention_heads=16,
           attention_dim=256)


def test_scalar_decay_scan_kernels_compile_for_the_v5e_at_published_widths(one_chip):
    """``gdn_fwd`` and ``gdn_bwd`` at the cell's shapes and the scalar branch's committed tiling
    (``kda.GDN_TILING``: eight chunks of 128 a grid step, whose ``gdn_bwd`` holds 17.61 MiB of
    scoped fast memory: compiled with the limit ``kda._params`` asks for, ``kda.VMEM_LIMIT``, and
    refused at Mosaic's own 16 MiB): Mosaic takes
    the lane select of a head's decay out of the ``[1024, 32]`` block beside β's and its broadcast
    along the lanes, the ``[128, 128]`` mask's product with the triangle of ones, the key
    head's block read by two value heads' programs (``h // 2`` in the index map), and the row that ``dg`` leaves as;
    the key heads' gradients leave a block a value head, ``[2, 8192, 4096]``, and are summed
    to ``[2, 8192, 2048]`` outside."""
    from csed_514_project_distributed_training_using_pytorch_tpu.ops import kda
    b, s, kh, h, d = (GDN[k] for k in ("batch", "seq", "key_heads", "heads", "head_dim"))
    spec = lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    keys, values = spec((b, s, kh * d), jnp.bfloat16), spec((b, s, h * d), jnp.bfloat16)
    scalars = spec((b, s, h), jnp.float32)
    loss = lambda *args: jnp.sum(kda.gdn_scan(*args, key_heads=kh, eps=1e-6).astype(jnp.float32))
    compile_pair = lambda: jax.jit(jax.grad(loss, argnums=(0, 1, 2, 3, 4))).lower(
        keys, keys, values, scalars, scalars).compile()
    assert kda._params().vmem_limit_bytes == kda.VMEM_LIMIT
    with lowering_for_the_chip(kda):
        compiled = compile_pair()
        with mosaics_own_limit(kda), pytest.raises(Exception, match="exceeded scoped vmem limit"):
            compile_pair()
    text = compiled.as_text()
    assert "%gdn_fwd" in text and "%gdn_bwd" in text and "%kda_" not in text
    chunk, sub, group = kda.GDN_TILING
    plan = kda.scan_plan(heads=h, key_heads=kh, key_dim=d, value_dim=d, seq_len=s)
    assert (plan["chunk"], plan["sub_block"], plan["group"]) == (chunk, sub, group)
    rows = group * chunk
    assert plan["states_per_sequence"] == s // rows
    assert f"f32[{b},{s // rows},{h},{d},{d}]" in text              # a state a group
    assert text.count(f"f32[{b},{h},{s // rows},1,{rows}]") >= 2    # dβ and dg, rows a program
    assert [x.shape for x in jax.tree.leaves(compiled.out_info)] == \
        [(b, s, kh * d)] * 2 + [(b, s, h * d)] + [(b, s, h)] * 2


def test_a_gated_delta_layer_keeps_the_states_its_plan_counts(one_chip):
    """``gdn_mixer`` of the published file, value and every gradient, at the cell's shapes: the
    file names no tiling, the model passes none (``kda_tiling`` None), and the states the
    compiled program keeps between ``gdn_fwd`` and ``gdn_bwd`` are the ``compile`` event's
    ``gdn`` plan's: one a group of the scalar branch's own tiling, ``f32[b, s // rows, h, d, d]``.
    A tiling Mosaic refuses for fast memory fails here, without a chip."""
    from csed_514_project_distributed_training_using_pytorch_tpu.models import hybrid_lm
    from csed_514_project_distributed_training_using_pytorch_tpu.ops import kda
    b, s, h, d = (GDN[k] for k in ("batch", "seq", "heads", "head_dim"))
    config = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                          "benchmark", "configs", "qwen3-next-80b-a3b-ep16.json")
    model = hybrid_lm.from_config_file(config, vocab_size=18992, seq_len=s,
                                       dtype=jnp.bfloat16, remat=True)
    plan = model.gdn_plan()
    assert model.kda_tiling is None and model.kda_plan() is None
    assert (plan["chunk"], plan["sub_block"], plan["group"]) == kda.GDN_TILING
    assert (plan["heads"], plan["key_heads"], plan["key_dim"]) == (h, GDN["key_heads"], d)
    on_chip = lambda tree: jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip), tree)
    p = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0)))["params"]["layer_0"]["gdn"]
    u = jax.ShapeDtypeStruct((b, s, model.hidden_size), jnp.bfloat16)
    loss = lambda p, u: jnp.sum(hybrid_lm.gdn_mixer(p, u, model).astype(jnp.float32))
    with lowering_for_the_chip(kda):
        text = jax.jit(jax.grad(loss, argnums=(0, 1))).lower(*on_chip((p, u))).compile().as_text()
    assert "%gdn_fwd" in text and "%gdn_bwd" in text and "%kda_" not in text
    kept = f"f32[{b},{plan['states_per_sequence']},{h},{d},{d}]"
    assert plan["state_bytes_per_sequence"] == plan["states_per_sequence"] * h * d * d * 4
    assert kept in text and f"f32[{b},{s // plan['chunk']},{h},{d},{d}]" not in text


def test_flash_kernels_compile_for_the_v5e_at_a_head_width_of_256(one_chip):
    """``flash_fwd`` and the fused backward (``flash_dkv``, 8.4 MB of float32 dq resident a
    (batch, head); no ``flash_dq``) at 256 / 256, two lane registers a head, at the blocks
    ``_flash_plan`` picks for 8192 tokens."""
    from csed_514_project_distributed_training_using_pytorch_tpu.ops import pallas_attention
    b, s, h, d = (GDN[k] for k in ("batch", "seq", "attention_heads", "attention_dim"))
    x = jax.ShapeDtypeStruct((b, s, h, d), jnp.bfloat16, sharding=one_chip)
    loss = lambda q, k, v: jnp.sum(pallas_attention.flash_attention(
        q, k, v, causal=True).astype(jnp.float32))
    with lowering_for_the_chip(pallas_attention):
        compiled = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(x, x, x).compile()
    text = compiled.as_text()
    plan = pallas_attention.dispatch_plan((b, s, h, d), causal=True)
    assert (plan["impl"], plan["backward"], plan["block"]) == ("flash", "fused", 1024)
    assert pallas_attention.backward_fused(s, d)
    assert "%flash_fwd" in text and "%flash_dkv" in text and "%flash_dq" not in text
    assert [x.shape[-1] for x in jax.tree.leaves(compiled.out_info)] == [d, d, d]
