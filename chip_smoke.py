#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the system still starts on the chip.

Drives the main paths once, through the entry points a user would call, at the
full width of the widest model both the trainer and the server take — the GQA
pixel LM, d_model 1024, 8 layers, 8 heads (head_dim 128), 2 KV heads, RoPE,
S=784, vocab 17 (``bench_results/hw_r5/bench_lm_large_tpu.json``) — with random
weights from a seed and the synthetic MNIST (``data_source: synthetic``):

========================  ====================================================
phase                     what it runs and checks
========================  ====================================================
``devices``               ``jax.devices()``: every device's platform is
                          ``tpu``; kind/count/versions printed; the peaks table
                          (``utils/benchmarks.py``) knows the kind
``train_distributed``     ``python -m …train.distributed`` (MNIST CNN), one
                          ``data`` axis over every visible chip: finite,
                          falling loss; eval NLL under the 2.30 baseline
``train_lm``              ``python -m …train.lm`` at full width, a few
                          optimizer steps, writes ``results/model_lm.ckpt``
``server``                ``serving.replica.build_engine_server`` from that
                          checkpoint behind ``serving.Server``: greedy requests
                          with prompt lengths 0/16/64/400, contiguous then
                          paged KV — all ``ok``, identical tokens, trace-count
                          pins hold (four chips: also ``--shard tp=2,dp=2``,
                          token-identical, shards on four distinct devices)
``kernels``               each Pallas kernel compiled (Mosaic custom call found
                          in the compiled program) against its in-repo
                          reference: flash fwd + the fused backward at S=2048,
                          D=128, bf16, causal; ``paged_attend`` at G=2, R=4,
                          D=128, page 64, fp32 and int8+scales; fused NLL/SGD
                          through ``train.single --use-pallas-kernels``
four chips only           ``placement`` (trainer shardings land on four distinct
                          devices), ``train_lm_tp`` (``--mesh data=2,model=2``),
                          ``smoke`` (ring ``ppermute`` in-process),
                          ``smoke_launch`` (``train.launch --num-processes 4``)
========================  ====================================================

A chip belongs to one process at a time, so this parent never imports jax: each
phase is one child process, run serially, and the parent only reads what the
children wrote. Any phase's failure is the script's failure (exit 1, no result
line); there is no retry, no other backend, no skipped phase. On one chip the
four-chip phases are not run and the summary says ``chips: 1`` — that is the
machine's size, not a skip.

Per phase the summary reports wall seconds, the compile/run split, and how many
entries the phase ADDED to the persistent compile cache (``cache_new`` 0 = every
program hit), so a second run on the same machine visibly costs seconds.

The line before last is ``chip_smoke: summary: {...}``: one JSON object with the
phases, versions, cache and ``"claim": null`` (this script claims no gain). The
last line of stdout is one JSON object with exactly two keys, the device as jax
reports it: ``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count":
...}}``.
"""

from __future__ import annotations

import argparse
import json
import math
import os
import shutil
import signal
import subprocess
import sys
import tempfile
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
PKG = "csed_514_project_distributed_training_using_pytorch_tpu"
DEADLINE_S = 1150.0          # the contract allows 1200 s, compilation included

# The full-width model (module docstring): S=784 and the 17-symbol vocabulary
# are the pixel stream's own and are not knobs.
LM_WIDTH = {"embed_dim": 1024, "num_layers": 8, "num_heads": 8, "kv_heads": 2}
FLASH_SHAPE = (2, 2048, 8, 128)      # B, S, H, D: the geometry hw_r5 measured
PAGED_GEOMETRY = {"b": 8, "g": 2, "rep": 4, "d": 128, "ps": 64, "s": 784}
PROMPT_LENS = (0, 16, 64, 400)       # cross every size in --prefill-chunks
PREFILL_CHUNKS = "32,128,512"
MAX_NEW_TOKENS = 8
UNIFORM_NLL = math.log(17)           # a 17-way uniform guess, nats per token


class SmokeFailure(Exception):
    """A phase ran and its outputs are wrong (or it did not run to an end)."""


def _require(cond: bool, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


# ============================================================================
# Child side: phases that need jax in-process (``--phase NAME --work DIR``)
# ============================================================================


def _tpu_devices():
    """``jax.devices()``, or SmokeFailure naming the platform when any device is
    not a TPU. Every in-process phase starts here: a process that lost the chip
    must not run a kernel interpreted, or a model on the host, without a word."""
    import jax

    devs = jax.devices()
    platforms = sorted({d.platform for d in devs})
    _require(platforms == ["tpu"],
             f"platform is {', '.join(platforms)}, not tpu: jax found no "
             f"accelerator (chip_smoke.py never runs on another backend)")
    return devs


class _CacheEvents:
    """Counts jax's persistent-compile-cache hits and misses in this process."""

    def __init__(self):
        from jax import monitoring

        self.hits = self.misses = 0
        monitoring.register_event_listener(self._on_event)

    def _on_event(self, name: str, **_):
        if name == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif name == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def summary(self) -> dict:
        return {"cache_hits": self.hits, "cache_misses": self.misses}


def _with_totals(parts: dict, **extra) -> dict:
    """A phase's summary: its named parts, plus their compile/run seconds summed."""
    return {**parts, **extra,
            "compile_s": round(sum(p["compile_s"] for p in parts.values()), 2),
            "run_s": round(sum(p["run_s"] for p in parts.values()), 2)}


def _mosaic_calls(compiled) -> int:
    """How many Mosaic (Pallas TPU) custom calls a compiled program contains."""
    return compiled.as_text().count("tpu_custom_call")


def phase_devices(work: str) -> dict:
    devs = _tpu_devices()          # first act, before anything else is imported

    import importlib.metadata

    import jax
    import jaxlib

    from csed_514_project_distributed_training_using_pytorch_tpu.utils.benchmarks import (
        peak_flops,
        peak_hbm_bytes,
    )

    kind = devs[0].device_kind
    _require(all(d.device_kind == kind for d in devs),
             f"mixed device kinds: {sorted({d.device_kind for d in devs})}")
    flops, hbm = peak_flops(kind), peak_hbm_bytes(kind)
    _require(flops is not None and hbm is not None,
             f"utils/benchmarks.py has no peak FLOP/s or HBM bytes/s for device "
             f"kind {kind!r}: an MFU of null on an unrecognised device is a "
             f"fallback too — add the kind to the tables")
    return {
        "device": {"platform": devs[0].platform, "kind": kind, "count": len(devs)},
        "versions": {"python": sys.version.split()[0], "jax": jax.__version__,
                     "jaxlib": jaxlib.__version__,
                     "libtpu": importlib.metadata.version("libtpu")},
        "peak_bf16_flops": flops, "peak_hbm_bytes_per_s": hbm,
    }


def _engine_args(checkpoint: str, **overrides) -> argparse.Namespace:
    """The namespace ``build_engine_server`` reads — the model/engine/server
    flags ``serving/replica.py::main`` and ``tools/serve_loadgen.py`` declare,
    at their defaults except the full-width model and what ``overrides`` set."""
    args = dict(
        checkpoint=checkpoint, seq_len=784, num_levels=16, **LM_WIDTH,
        attention_window=0, rope=True, seed=0, num_slots=8, max_pending=128,
        timeout_s=0.0, prefill_chunks=PREFILL_CHUNKS, prefill_budget=1, prefix_cache=0,
        prefix_cache_bytes=0, kv_layout="contiguous", page_size=64, num_pages=0,
        kv_dtype="model", quant_policy="off", spec="off", spec_k=4, warmup=1,
        slo="", tenants="", shard="", telemetry="", trace="")
    args.update(overrides)
    return argparse.Namespace(**args)


def _trace_pins(engine) -> dict:
    pins = {"decode": engine.trace_count, "admit": engine.admit_trace_count,
            "prefill": dict(sorted(engine.prefill_trace_counts.items()))}
    if hasattr(engine, "cow_trace_count"):
        pins["cow"] = engine.cow_trace_count
    return pins


def _distinct_shard_devices(tree) -> int:
    """Over every leaf of ``tree``: the largest number of distinct devices one
    leaf's addressable shards sit on."""
    import jax

    return max(len({s.device for s in leaf.addressable_shards})
               for leaf in jax.tree_util.tree_leaves(tree))


def _serve_once(label: str, prompts, checkpoint: str,
                **overrides) -> tuple[dict, list]:
    """Build one engine+server through the fleet's own builder, answer the
    prompts greedily, check every completion and the trace-count pins.
    Returns ``(summary, token lists)``."""
    import jax
    import numpy as np

    from csed_514_project_distributed_training_using_pytorch_tpu.serving.replica import (
        build_engine_server,
    )

    t0 = time.perf_counter()
    engine, server = build_engine_server(_engine_args(checkpoint, **overrides))
    build_s = time.perf_counter() - t0        # init + checkpoint + warmup compiles
    pins = _trace_pins(engine)
    chunk_sizes = [int(c) for c in PREFILL_CHUNKS.split(",")]
    _require(pins["decode"] == 1 and pins["admit"] == 1
             and pins["prefill"] == {c: 1 for c in chunk_sizes},
             f"{label}: warmup should trace each program once, got {pins}")
    t0 = time.perf_counter()
    with server:
        futures = [server.submit(p, max_new_tokens=MAX_NEW_TOKENS)
                   for p in prompts]
        comps = [f.result(timeout=300) for f in futures]
    serve_s = time.perf_counter() - t0
    for p, c in zip(prompts, comps):
        _require(c.finish == "ok", f"{label}: request with prompt length "
                                   f"{len(p)} finished {c.finish!r}")
        _require(c.new_tokens == MAX_NEW_TOKENS
                 and c.tokens.shape == (len(p) + MAX_NEW_TOKENS,),
                 f"{label}: prompt length {len(p)} returned {c.tokens.shape} "
                 f"tokens, {c.new_tokens} new")
        _require(np.array_equal(c.tokens[:len(p)], p),
                 f"{label}: completion does not start with its prompt")
        _require(bool(np.all((c.tokens >= 0) & (c.tokens <= 16))),
                 f"{label}: token outside the 17-symbol vocabulary")
    _require(_trace_pins(engine) == pins,
             f"{label}: a program retraced after warmup: {pins} -> "
             f"{_trace_pins(engine)}")
    result = {"compile_s": round(build_s, 2), "run_s": round(serve_s, 2),
              "trace_pins": pins, "ttft_s": [round(c.ttft_s, 4) for c in comps]}
    if overrides.get("shard"):
        n = len(jax.devices())
        on_params = _distinct_shard_devices(engine.params)
        on_cache = _distinct_shard_devices(engine._cache)
        _require(on_params == n and on_cache == n,
                 f"{label}: shards sit on {on_params} (params) / {on_cache} "
                 f"(KV cache) distinct devices, expected {n}")
        # tp=2 halves the KV-head axis, dp=2 halves the slot axis.
        k = jax.tree_util.tree_leaves(engine._cache)[0]
        shard_shape = k.addressable_shards[0].data.shape
        _require(shard_shape == (k.shape[0] // 2, k.shape[1], k.shape[2] // 2,
                                 k.shape[3]),
                 f"{label}: KV plane {k.shape} sharded as {shard_shape}")
        result["shards"] = {"param_devices": on_params, "cache_devices": on_cache,
                            "kv_plane": list(k.shape),
                            "kv_shard": list(shard_shape)}
    return result, [c.tokens.tolist() for c in comps]


def phase_server(work: str) -> dict:
    import numpy as np

    devs = _tpu_devices()
    checkpoint = os.path.join(work, "results", "model_lm.ckpt")
    _require(os.path.exists(checkpoint),
             f"{checkpoint} is missing — the train_lm phase writes it")
    rng = np.random.default_rng(21)
    prompts = [rng.integers(0, 16, size=n).astype(np.int32) for n in PROMPT_LENS]

    layouts = {"contiguous": {"kv_layout": "contiguous"},
               "paged": {"kv_layout": "paged"}}
    if len(devs) >= 4:
        layouts["tp2_dp2"] = {"shard": "tp=2,dp=2"}
    engines, reference = {}, None
    for name, kw in layouts.items():
        engines[name], tokens = _serve_once(name, prompts, checkpoint, **kw)
        reference = tokens if reference is None else reference
        _require(tokens == reference,
                 f"{name} engine's tokens differ from the contiguous one-chip "
                 f"engine's")
    return _with_totals(engines, token_identical=list(engines))


def _check_flash() -> dict:
    """Flash forward and the fused backward through ``dispatch_attention`` at the
    geometry hw_r5 measured (S=2048, D=128, bf16, causal) vs the dense oracle,
    at the tolerances of tests/test_pallas_attention.py's TPU-gated test."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from csed_514_project_distributed_training_using_pytorch_tpu.ops.attention import (
        full_attention,
    )
    from csed_514_project_distributed_training_using_pytorch_tpu.ops.pallas_attention import (
        dispatch_attention,
        dispatch_plan,
    )

    b, s, h, d = FLASH_SHAPE
    _require(dispatch_plan(FLASH_SHAPE, causal=True)["impl"] == "flash",
             f"dispatch_attention gives way to dense at {FLASH_SHAPE}")
    rng = np.random.default_rng(4)
    q, k, v = (jnp.asarray(rng.normal(size=(b, s, h, d)), jnp.bfloat16)
               for _ in range(3))
    q32, k32, v32 = (x.astype(jnp.float32) for x in (q, k, v))

    def loss(attn):
        return lambda q, k, v: jnp.sum(jnp.sin(attn(q, k, v).astype(jnp.float32)))

    flash = lambda q, k, v: dispatch_attention(q, k, v, causal=True)
    dense = lambda q, k, v: full_attention(q, k, v, causal=True)
    t0 = time.perf_counter()
    fwd = jax.jit(flash).lower(q, k, v).compile()
    bwd = jax.jit(jax.grad(loss(flash), argnums=(0, 1, 2))).lower(q, k, v).compile()
    compile_s = time.perf_counter() - t0
    calls = {"fwd": _mosaic_calls(fwd), "fwd+bwd": _mosaic_calls(bwd)}
    _require(calls["fwd"] >= 1 and calls["fwd+bwd"] >= 2,
             f"flash: compiled programs hold {calls} Mosaic custom calls; "
             f"expected the forward kernel, and forward + the fused backward")
    t0 = time.perf_counter()
    out = np.asarray(fwd(q, k, v).astype(jnp.float32))
    grads = [np.asarray(g.astype(jnp.float32)) for g in bwd(q, k, v)]
    run_s = time.perf_counter() - t0
    ref = np.asarray(dense(q32, k32, v32))
    ref_grads = jax.grad(loss(dense), argnums=(0, 1, 2))(q32, k32, v32)
    np.testing.assert_allclose(out, ref, rtol=2e-2, atol=2e-2)
    for name, g, r in zip("qkv", grads, ref_grads):
        _require(bool(np.all(np.isfinite(g))), f"flash: d{name} is not finite")
        np.testing.assert_allclose(g, np.asarray(r), rtol=2e-2, atol=5e-2,
                                   err_msg=f"d{name}")
    return {"mosaic_calls": calls, "compile_s": round(compile_s, 2),
            "run_s": round(run_s, 3),
            "max_abs_err": round(float(np.max(np.abs(out - ref))), 5)}


def _check_paged_attend(quantized: bool) -> dict:
    """``paged_attend`` compiled at the engine's own geometry (the full-width
    model's G=2 KV heads x R=4 queries each, D=128, page 64, S=784) vs
    ``paged_attend_reference`` on a shuffled page table."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from csed_514_project_distributed_training_using_pytorch_tpu.ops import (
        quant as quant_ops,
    )
    from csed_514_project_distributed_training_using_pytorch_tpu.ops.paged_attention import (
        paged_attend,
        paged_attend_reference,
    )

    b, g, rep, d, ps, s = (PAGED_GEOMETRY[k] for k in
                           ("b", "g", "rep", "d", "ps", "s"))
    p_max = -(-s // ps)
    num_pages = 1 + b * p_max + 2           # null page + every slot's + spares
    rng = np.random.default_rng(7)
    k_pool = jnp.asarray(rng.normal(size=(num_pages, ps, g, d)), jnp.float32)
    v_pool = jnp.asarray(rng.normal(size=(num_pages, ps, g, d)), jnp.float32)
    scales = {}
    if quantized:
        k_pool, k_scale = quant_ops.quantize_rows(k_pool, jnp.int8)
        v_pool, v_scale = quant_ops.quantize_rows(v_pool, jnp.int8)
        scales = {"k_scale": k_scale, "v_scale": v_scale}
    ids = np.arange(1, 1 + b * p_max)
    rng.shuffle(ids)                        # non-contiguous page assignment
    table = jnp.asarray(ids.reshape(b, p_max), jnp.int32)
    q = jnp.asarray(rng.normal(size=(b, g, rep, d)), jnp.float32)
    t = rng.integers(0, s, size=b).astype(np.int32)
    t[0], t[-1] = 0, s - 1                  # the edge positions too
    t = jnp.asarray(t)

    kernel = jax.jit(lambda q, kp, vp, table, t, sc: paged_attend(
        q, kp, vp, table, t, interpret=False, **sc))
    t0 = time.perf_counter()
    compiled = kernel.lower(q, k_pool, v_pool, table, t, scales).compile()
    compile_s = time.perf_counter() - t0
    calls = _mosaic_calls(compiled)
    _require(calls >= 1, "paged_attend: no Mosaic custom call in the compiled "
                         "program")
    t0 = time.perf_counter()
    out = np.asarray(compiled(q, k_pool, v_pool, table, t, scales))
    run_s = time.perf_counter() - t0
    ref = np.asarray(paged_attend_reference(
        q, k_pool, v_pool, table, t, seq_len=s, **scales))
    _require(out.shape == (b, g, rep, d) and bool(np.all(np.isfinite(out))),
             f"paged_attend: output {out.shape}, finite "
             f"{bool(np.all(np.isfinite(out)))}")
    # Both sides run their f32 matmuls as bf16 MXU passes and differ at ~1e-3
    # (measured 0.0015, PR 21) — the tolerance of the repo's other TPU-gated
    # kernel checks.
    np.testing.assert_allclose(out, ref, rtol=2e-2, atol=2e-2)
    return {"mosaic_calls": calls, "compile_s": round(compile_s, 2),
            "run_s": round(run_s, 3),
            "max_abs_err": round(float(np.max(np.abs(out - ref))), 5)}


def _check_fused_loss_and_sgd(work: str) -> dict:
    """The fused NLL and SGD-momentum kernels through ``train.single
    --use-pallas-kernels`` for a few steps: per-step loss equal to the XLA
    path's within tests/test_pallas.py's step-parity tolerance."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from csed_514_project_distributed_training_using_pytorch_tpu.data import (
        load_mnist,
    )
    from csed_514_project_distributed_training_using_pytorch_tpu.models.cnn import (
        Net,
    )
    from csed_514_project_distributed_training_using_pytorch_tpu.train import single
    from csed_514_project_distributed_training_using_pytorch_tpu.train.step import (
        create_train_state,
        make_train_step,
    )
    from csed_514_project_distributed_training_using_pytorch_tpu.utils.config import (
        SingleProcessConfig,
    )

    model = Net()
    step = jax.jit(make_train_step(model, learning_rate=0.01, momentum=0.5,
                                   use_pallas=True))
    t0 = time.perf_counter()
    compiled = step.lower(
        create_train_state(model, jax.random.PRNGKey(0)),
        jnp.zeros((64, 28, 28, 1), jnp.float32), jnp.zeros((64,), jnp.int32),
        jax.random.PRNGKey(1)).compile()
    compile_s = time.perf_counter() - t0
    calls = _mosaic_calls(compiled)
    _require(calls >= 3, f"use_pallas train step holds {calls} Mosaic custom "
                         f"calls; expected the NLL forward, its backward and "
                         f"the SGD update")

    datasets = load_mnist(os.path.join(work, "files"))
    steps = 5
    t0 = time.perf_counter()
    losses = {}
    for use_pallas in (False, True):
        out_dir = os.path.join(work, f"single_{'pallas' if use_pallas else 'xla'}")
        _, history = single.main(
            SingleProcessConfig(
                n_epochs=1, max_train_examples=64 * steps, max_test_examples=1000,
                log_interval=1, use_pallas_kernels=use_pallas,
                results_dir=os.path.join(out_dir, "results"),
                images_dir=os.path.join(out_dir, "images")),
            datasets=datasets)
        losses[use_pallas] = np.asarray(history.train_losses[:steps])
    run_s = time.perf_counter() - t0
    _require(len(losses[True]) == steps and bool(np.all(np.isfinite(losses[True]))),
             f"train.single --use-pallas-kernels losses: {losses[True]}")
    np.testing.assert_allclose(losses[True], losses[False], rtol=1e-5, atol=1e-6)
    return {"mosaic_calls": calls, "compile_s": round(compile_s, 2),
            "run_s": round(run_s, 2),
            "losses": [round(float(x), 6) for x in losses[True]],
            "max_abs_diff_vs_xla": float(np.max(np.abs(losses[True]
                                                        - losses[False])))}


def phase_kernels(work: str) -> dict:
    _tpu_devices()
    return _with_totals({
        "flash_s2048_d128_bf16_causal": _check_flash(),
        "paged_attend_fp32": _check_paged_attend(quantized=False),
        "paged_attend_int8": _check_paged_attend(quantized=True),
        "fused_nll_sgd_train_single": _check_fused_loss_and_sgd(work)})


def phase_placement(work: str) -> dict:
    """Four chips: the trainers' own placement helpers put their shards on four
    distinct devices with the expected shard shapes. ``make_mesh`` takes
    ``jax.devices()[:n]``; code that has only met virtual CPU devices could put
    everything on the first."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import PartitionSpec as P

    from csed_514_project_distributed_training_using_pytorch_tpu.models import (
        lm as lm_mod,
    )
    from csed_514_project_distributed_training_using_pytorch_tpu.models.cnn import (
        Net,
    )
    from csed_514_project_distributed_training_using_pytorch_tpu.parallel import (
        data_parallel as dp,
        tensor_parallel as tp,
    )
    from csed_514_project_distributed_training_using_pytorch_tpu.parallel.mesh import (
        make_mesh,
    )
    from csed_514_project_distributed_training_using_pytorch_tpu.train.step import (
        create_train_state,
    )

    devs = _tpu_devices()
    n = len(devs)
    _require(n == 4, f"placement phase is written for four chips, found {n}")

    def shards(x):
        return (len({s.device for s in x.addressable_shards}),
                {tuple(s.data.shape) for s in x.addressable_shards})

    # train.distributed: batch sharded over `data`, state replicated.
    mesh = make_mesh()
    batch = dp.put_global(mesh, np.zeros((64, 28, 28, 1), np.float32), P("data"))
    _require(shards(batch) == (4, {(16, 28, 28, 1)}),
             f"data=4 batch shards: {shards(batch)}")
    state = jax.device_put(create_train_state(Net(), jax.random.PRNGKey(0)),
                           dp.replicated(mesh))
    leaf = jax.tree_util.tree_leaves(state.params)[0]
    _require(shards(leaf) == (4, {tuple(leaf.shape)}),
             f"replicated CNN leaf shards: {shards(leaf)}")

    # train.lm --mesh data=2,model=2 at full width (one layer is enough to see
    # the Megatron column/row split).
    mesh = make_mesh(4, axis_names=("data", "model"), axis_shape=(2, 2))
    model = lm_mod.TransformerLM(
        vocab_size=17, seq_len=784, embed_dim=LM_WIDTH["embed_dim"], num_layers=1,
        num_heads=LM_WIDTH["num_heads"], num_kv_heads=LM_WIDTH["kv_heads"],
        rope=True, dtype=jnp.bfloat16)
    lm_state = tp.shard_train_state(
        mesh, create_train_state(model, jax.random.PRNGKey(0),
                                 sample_input_shape=(1, 784)))
    split = {}
    for path, x in jax.tree_util.tree_leaves_with_path(lm_state.params):
        count, shapes = shards(x)
        _require(count == 4, f"{jax.tree_util.keystr(path)} sits on {count} "
                             f"devices")
        if shapes != {tuple(x.shape)}:
            split[jax.tree_util.keystr(path)] = (tuple(x.shape), shapes)
    _require(split, "data=2,model=2: no LM parameter is model-sharded")
    for name, (full, shapes) in split.items():
        _require(len(shapes) == 1 and math.prod(next(iter(shapes))) * 2
                 == math.prod(full),
                 f"{name} {full} should split in two over `model`, got {shapes}")
    return {"data4_batch_shard": [16, 28, 28, 1], "replicated_on": 4,
            "tp_split_leaves": len(split),
            "tp_example": {k: [list(v[0]), [list(s) for s in v[1]]]
                           for k, v in list(split.items())[:2]},
            "compile_s": 0.0, "run_s": 0.0}


IN_PROCESS_PHASES = {"devices": phase_devices, "server": phase_server,
                     "kernels": phase_kernels, "placement": phase_placement}


def run_child(name: str, work: str) -> int:
    """``--phase NAME``: run one in-process phase, write ``<work>/<NAME>.json``."""
    sys.path.insert(0, ROOT)
    from csed_514_project_distributed_training_using_pytorch_tpu.utils.compile_cache import (
        enable_compile_cache,
    )

    enable_compile_cache()
    events = _CacheEvents()
    try:
        result = IN_PROCESS_PHASES[name](work)
    except (SmokeFailure, AssertionError) as e:
        print(f"chip_smoke: phase {name} FAILED: {e}", file=sys.stderr)
        return 1
    result.update(events.summary())
    with open(os.path.join(work, f"{name}.json"), "w") as f:
        json.dump(result, f)
    return 0


# ============================================================================
# Parent side: no jax. Runs the phases as serial children and reads their files.
# ============================================================================


def _read_jsonl(path: str) -> list[dict]:
    from csed_514_project_distributed_training_using_pytorch_tpu.utils.jsonl import (
        read_jsonl,
    )

    return read_jsonl(path)


def _trainer_telemetry(work: str, name: str, chips: int) -> tuple[list, dict]:
    """Epoch events + timing of a trainer phase, after checking its manifest
    says it ran on every visible chip of a TPU."""
    rows = _read_jsonl(os.path.join(work, f"{name}.jsonl"))
    manifest = next(r for r in rows if r["event"] == "manifest")
    _require(manifest["platform"] == "tpu" and manifest["device_count"] == chips,
             f"{name} ran on {manifest['device_count']} x {manifest['platform']}, "
             f"expected {chips} x tpu")
    epochs = [r for r in rows if r["event"] == "epoch"]
    _require(bool(epochs), f"{name} wrote no epoch event")
    compile_s = sum((r.get("lower_s") or 0) + (r.get("compile_s") or 0)
                    for r in rows if r["event"] == "compile")
    run_s = sum((r.get("execute_s") or 0) + (r.get("eval_s") or 0) for r in epochs)
    timing = {"compile_s": round(compile_s, 2), "run_s": round(run_s, 2),
              "mesh": (manifest.get("mesh") or {}).get("shape")}
    return epochs, timing


def _stderr_field(work: str, name: str, prefix: str) -> str | None:
    with open(os.path.join(work, f"{name}.log")) as f:
        for line in f:
            if line.startswith(prefix):
                return line[len(prefix):].strip()
    return None


def check_train_distributed(work: str, chips: int) -> dict:
    epochs, out = _trainer_telemetry(work, "train_distributed", chips)
    losses = [e["train_loss"] for e in epochs]
    _require(all(l is not None and math.isfinite(l) for l in losses)
             and losses[-1] < losses[0],
             f"train.distributed loss is not finite and falling: {losses}")
    steps = [r["loss"] for r in _read_jsonl(os.path.join(work, "results",
                                                         "metrics.jsonl"))
             if r["kind"] == "train"]
    _require(steps[-1] < steps[0], f"per-step loss did not fall: {steps[0]} -> "
                                   f"{steps[-1]}")
    _require(epochs[-1]["val_loss"] < 2.30,
             f"post-run eval NLL {epochs[-1]['val_loss']} is not under the 2.30 "
             f"untrained baseline")
    native = _stderr_field(work, "train_distributed", "data.native: ")
    _require(native is not None and not native.startswith("numpy (build failed"),
             f"native loader: {native}")
    out.update(train_loss=[round(l, 4) for l in losses],
               val_nll=round(epochs[-1]["val_loss"], 4), native_loader=native)
    return out


def check_train_lm(work: str, chips: int, name: str = "train_lm") -> dict:
    epochs, out = _trainer_telemetry(work, name, chips)
    train = [e["train_loss"] for e in epochs]
    val = [e["val_loss"] for e in epochs]
    _require(all(x is not None and math.isfinite(x) for x in train + val)
             and train[-1] < train[0],
             f"{name} loss is not finite and falling: train {train}, val {val}")
    # A few AdamW steps on pixel streams (mostly background) already beat the
    # 17-way uniform guess a random init starts at.
    _require(val[-1] < UNIFORM_NLL,
             f"{name} val NLL/token {val[-1]:.4f} is not under the uniform "
             f"{UNIFORM_NLL:.4f}")
    out.update(steps=sum(e["steps"] for e in epochs),
               train_loss=[round(x, 4) for x in train],
               val_nll_per_token=[round(x, 4) for x in val])
    return out


def check_train_lm_checkpoint(work: str, chips: int) -> dict:
    out = check_train_lm(work, chips)
    ckpt = os.path.join(work, "results", "model_lm.ckpt")
    _require(os.path.exists(ckpt) and os.path.getsize(ckpt) > 0,
             f"{ckpt} was not written")
    out["checkpoint_mb"] = round(os.path.getsize(ckpt) / 2 ** 20, 1)
    return out


def check_train_lm_tp(work: str, chips: int) -> dict:
    out = check_train_lm(work, chips, "train_lm_tp")
    _require(out["mesh"] == {"data": 2, "model": 2}, f"mesh: {out['mesh']}")
    return out


def check_smoke_log(name: str, processes: int):
    def check(work: str, chips: int) -> dict:
        with open(os.path.join(work, f"{name}.log")) as f:
            log = f.read()
        _require("smoke: OK" in log, f"{name}: no 'smoke: OK' line")
        _require(f"smoke: {processes} process(es), {chips}-device mesh" in log,
                 f"{name}: expected {processes} process(es) on a {chips}-device "
                 f"mesh")
        return {"processes": processes, "devices": chips}
    return check


def check_in_process(name: str):
    def check(work: str, chips: int) -> dict:
        with open(os.path.join(work, f"{name}.json")) as f:
            return json.load(f)
    return check


def phases_for(chips: int, work: str) -> list[tuple[str, list[str], object]]:
    """``(name, python argv, parent-side check)`` in run order."""
    me = os.path.abspath(__file__)

    def module(name: str) -> list[str]:
        return ["-m", f"{PKG}.train.{name}"]

    def in_process(name: str):
        return (name, [me, "--phase", name, "--work", work], check_in_process(name))

    def lm_run(tag: str, layers: int, *extra: str) -> list[str]:
        """train.lm at full width: 2 epochs x 4 optimizer steps (64 examples at
        batch 16). At the default 1e-3 the first AdamW steps of a d1024 model
        overshoot (epoch-0 val NLL 3.80 > uniform, PR 21 chip run); 3e-4 falls."""
        return module("lm") + [
            "--embed-dim", str(LM_WIDTH["embed_dim"]), "--num-layers", str(layers),
            "--num-heads", str(LM_WIDTH["num_heads"]),
            "--kv-heads", str(LM_WIDTH["kv_heads"]), "--rope", "--bf16",
            "--batch-size", "16",
            "--epochs", "2", "--learning-rate", "3e-4",
            "--max-train-examples", "64", "--max-test-examples", "32",
            "--eval-batch", "16", "--generate", "0",
            "--results-dir", f"results{tag}", "--images-dir", f"images{tag}",
            "--telemetry", f"train_lm{tag}.jsonl", *extra]

    phases = [
        in_process("devices"),
        ("train_distributed",
         module("distributed") + [
             "--epochs", "2", "--max-train-examples", "8192",
             "--max-test-examples", "2000", "--results-dir", "results",
             "--images-dir", "images", "--telemetry", "train_distributed.jsonl"],
         check_train_distributed),
        ("train_lm", lm_run("", LM_WIDTH["num_layers"]),
         check_train_lm_checkpoint),
        in_process("server"),
        in_process("kernels"),
    ]
    if chips >= 4:
        phases += [
            in_process("placement"),
            # Depth cut to 2: the mesh, not the layer count, is what this adds.
            ("train_lm_tp", lm_run("_tp", 2, "--mesh", "data=2,model=2"),
             check_train_lm_tp),
            ("smoke", module("smoke"), check_smoke_log("smoke", 1)),
            ("smoke_launch",
             module("launch") + ["--num-processes", "4", "--timeout", "300", "--"]
             + module("smoke"),
             check_smoke_log("smoke_launch", 4)),
        ]
    return phases


def _cache_entries(cache_dir: str) -> int:
    try:
        return sum(name.endswith("-cache") for name in os.listdir(cache_dir))
    except FileNotFoundError:
        return 0


def _run_phase(name: str, argv: list[str], work: str, timeout: float) -> None:
    """One child to its end, output to ``<work>/<name>.log``. The child leads its
    own process group, and the whole group is gone when this returns."""
    env = dict(os.environ)
    env["PYTHONPATH"] = (f"{ROOT}{os.pathsep}{env['PYTHONPATH']}"
                         if env.get("PYTHONPATH") else ROOT)
    log_path = os.path.join(work, f"{name}.log")
    with open(log_path, "w") as log:
        proc = subprocess.Popen([sys.executable, *argv], cwd=work, env=env,
                                stdout=log, stderr=subprocess.STDOUT,
                                start_new_session=True)
        try:
            rc = proc.wait(timeout=timeout)
        except subprocess.TimeoutExpired:
            rc = None
        finally:
            try:
                os.killpg(proc.pid, signal.SIGKILL)
            except ProcessLookupError:
                pass
            proc.wait()
    if rc != 0:
        with open(log_path) as f:
            tail = f.read()[-4000:]
        # A phase that checked and refused says why in one line; anything else
        # (a traceback, a timeout) is shown as the end of the child's output.
        for line in reversed(tail.splitlines()):
            if line.startswith(f"chip_smoke: phase {name} FAILED: "):
                raise SmokeFailure(line[len("chip_smoke: "):])
        raise SmokeFailure(
            f"phase {name} "
            + (f"exceeded {timeout:.0f}s" if rc is None else f"exited {rc}")
            + f"; end of its output:\n{tail}")


def main() -> int:
    if not os.path.isdir(os.path.join(ROOT, PKG)):
        print(f"chip_smoke: {PKG}/ is not next to this script — run it from a "
              f"checkout of the repo", file=sys.stderr)
        return 2
    sys.path.insert(0, ROOT)
    from csed_514_project_distributed_training_using_pytorch_tpu.utils.compile_cache import (
        DEFAULT_CACHE_DIR,
    )

    cache_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR") or DEFAULT_CACHE_DIR
    start = time.monotonic()
    work = tempfile.mkdtemp(prefix="chip_smoke_")
    keep = os.path.join(ROOT, "chiprun_out", "chip_smoke")     # logs, for a reader
    summary: dict = {}

    def run(phase, chips: int) -> dict:
        name, argv, check = phase
        before = _cache_entries(cache_dir)
        t0 = time.monotonic()
        _run_phase(name, argv, work, DEADLINE_S - (t0 - start))
        result = check(work, chips)
        result["wall_s"] = round(time.monotonic() - t0, 1)
        result["cache_new"] = _cache_entries(cache_dir) - before
        print(f"chip_smoke: {name}: ok  wall {result['wall_s']}s  compile "
              f"{result.get('compile_s', '-')}s  run {result.get('run_s', '-')}s  "
              f"cache_new {result['cache_new']}", flush=True)
        return result

    try:
        # The devices phase decides the machine's size; nothing else runs
        # before it has seen every device on the TPU platform.
        info = run(phases_for(1, work)[0], 1)
        chips = info["device"]["count"]
        print(f"chip_smoke: platform: {info['device']['platform']}  "
              f"kind: {info['device']['kind']}  count: {chips}  "
              f"versions: {info['versions']}  cache: {cache_dir}", flush=True)
        for phase in phases_for(chips, work)[1:]:
            summary[phase[0]] = run(phase, chips)
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED after {time.monotonic() - start:.0f}s: {e}",
              file=sys.stderr)
        return 1
    finally:
        os.makedirs(keep, exist_ok=True)
        for entry in os.listdir(work):
            if entry.endswith((".log", ".json", ".jsonl")):
                shutil.copy(os.path.join(work, entry), keep)
        shutil.rmtree(work, ignore_errors=True)

    report = {
        "device": info["device"],
        "chips": chips,
        "versions": info["versions"],
        "data_source": "synthetic",
        "native_loader": summary["train_distributed"]["native_loader"],
        "compile_cache": {"dir": cache_dir,
                          "new_entries": sum(p["cache_new"]
                                             for p in summary.values())},
        "wall_s": round(time.monotonic() - start, 1),
        "phases": summary,
        "claim": None,
    }
    print(f"chip_smoke: summary: {json.dumps(report)}")
    # The result line carries exactly these two keys: the driver reads it, and
    # everything else about the run is in the summary line above it.
    print(json.dumps({"ok": True, "device": info["device"]}), flush=True)
    return 0


if __name__ == "__main__":
    if "--phase" in sys.argv:
        p = argparse.ArgumentParser()
        p.add_argument("--phase", choices=sorted(IN_PROCESS_PHASES), required=True)
        p.add_argument("--work", required=True)
        ns = p.parse_args()
        sys.exit(run_child(ns.phase, ns.work))
    sys.exit(main())
