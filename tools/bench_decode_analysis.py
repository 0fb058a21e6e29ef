"""Where does small-config decode time go? — the r4 verdict item 6 analysis.

``bench_lm.py``'s d=256 decode sits at 19-44% of the HBM roofline where the d=1024
config hits 92%. The chained two-point protocol already cancels the fixed per-dispatch
HOST cost, so whatever remains is on-device. This tool decomposes it:

1. ``t_token`` — measured per-token seconds (chained protocol over full
   ``generate`` calls, exactly bench_lm's measurement);
2. ``t_roofline`` — the HBM bound for one token (cache re-read + amortized
   weights, bench_lm's accounting);
3. ``ops_per_token`` — executable-op count of ONE compiled decode step, read from
   the optimized HLO of ``jax.jit(decode_step).lower(...).compile()`` (fusions,
   copies, custom calls — everything the TensorCore sequencer must launch);
4. ``per_op_overhead_s = (t_token - t_roofline) / ops_per_token``.

If the per-op overhead lands at the TPU's known fixed per-kernel cost (~1-5 µs),
the residual is the DEVICE's per-op launch floor at a model size whose math is
microseconds — an op-count problem (fusing the step), not a bandwidth or dispatch
problem. The artifact makes that attribution explicit.

``--ttft-curve`` adds the serving-side decomposition this tool exists to make
explicit post-prefill: the TTFT-vs-prompt-length curve of the continuous-batching
engine with chunked batched prefill ON vs OFF (prefill-as-decode), plus the
prefill-vs-decode wall-clock split of the ON path. Off pays P sequential decode
invocations before the first generated token; on pays ``ceil(P/chunk)`` wide
forwards — the curve is the before/after record of that schedule change.

``--quant-ab`` runs the quantized-execution A/B this tool's roofline accounting
exists to verify: the SAME greedy workload through a fp32-oracle engine (A) and
a quantized engine (B: ``--ab-kv-dtype``/``--ab-quant-policy``), reporting (1)
**measured** decode bytes/token and KV bytes/slot from the live buffers of each
engine (``byte_accounting()`` — int8 planes and their scale planes priced at
their real itemsize, never a dtype assumption), and slots under the same HBM
budget; (2) the ACCURACY BUDGET: greedy token-match rate vs the fp32 oracle and
the teacher-forced NLL delta through the serving decode path (``--checkpoint``
for real weights); (3) the compile pins: the quantized engine must still trace
exactly one decode program and <= 1 prefill program per chunk size. The output
JSON is the committed ``bench_results/`` artifact format.

``--paged-ab`` runs the paged-KV layout A/B (``bench_results/paged_kv_cpu/``):
the SAME mixed short/long greedy workload through a contiguous-oracle engine
and a ``kv_layout="paged"`` engine — token identity (the adapters' bitwise
contract), measured slots-at-HBM-budget from the workload's actual page
reservations, and the long-prompt TTFT/TPOT tails that prove capacity wasn't
bought by taxing full-context requests.

All byte accounting in this tool is **byte-true**: cache and weight bytes are
summed from the actual arrays a run holds (``ops.quant.tree_bytes``), so a
quantized run's roofline denominator shrinks exactly as far as its buffers did.

Usage: ``python tools/bench_decode_analysis.py [--d-model 256 ...]`` — ONE JSON
line; CPU-drivable at tiny shapes (the op count is platform-specific, so the
committed artifact must come from a TPU run).
"""

from __future__ import annotations

import argparse
import json
import os
import re
import sys

# Script-mode import path: ``python tools/bench_decode_analysis.py`` puts tools/
# on sys.path, not the repo root the package lives in.
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def ttft_curve(model, params, args) -> list[dict]:
    """TTFT vs prompt length, chunked prefill ON vs OFF, one row per length.

    Each mode reuses ONE engine across the whole curve (slot recycling), with a
    max-length warmup request first, so every chunk size and the decode program
    are compiled before anything is timed — the curve measures the schedule, not
    XLA. The ON rows also split the request wall into prefill (chunk programs)
    vs decode (token steps)."""
    import time as _time

    import numpy as np

    from csed_514_project_distributed_training_using_pytorch_tpu.serving import (
        ContinuousBatchingEngine, Request,
    )

    lens = [int(x) for x in args.curve_prompt_lens.split(",") if x]
    lens = [l for l in lens if 0 < l < args.seq] or [args.seq // 2]
    chunks = tuple(int(x) for x in args.curve_chunks.split(",") if x)
    rng = np.random.default_rng(0)
    prompts = {p_len: rng.integers(0, args.vocab, size=p_len).astype(np.int32)
               for p_len in lens}
    warm = rng.integers(0, args.vocab, size=max(lens)).astype(np.int32)

    def measure(chunk_sizes):
        eng = ContinuousBatchingEngine(model, params, num_slots=1,
                                       prefill_chunk_sizes=chunk_sizes)
        # Warm ONE request per configured size (a length-c prompt plans as
        # exactly one c-chunk) plus a full-length one — a single max-length
        # warmup would never compile the sizes its greedy plan skips, and the
        # first short measured row would then time XLA instead of the schedule.
        for c in eng.prefill_chunk_sizes:
            eng.run([Request(prompt=warm[:min(c, args.seq - 1)],
                             max_new_tokens=1)])
        eng.run([Request(prompt=warm, max_new_tokens=2)])
        eng.reset_stats()
        rows = {}
        for p_len in lens:
            pre0, inv0 = eng.prefill_wall_s, eng.prefill_invocations
            t0 = _time.monotonic()
            comp = eng.run([Request(prompt=prompts[p_len],
                                    max_new_tokens=args.curve_new_tokens)])[0]
            wall = _time.monotonic() - t0
            prefill_s = eng.prefill_wall_s - pre0
            rows[p_len] = {
                "ttft_s": comp.ttft_s, "wall_s": wall,
                "prefill_wall_s": prefill_s,
                "decode_wall_s": wall - prefill_s,
                "prefill_invocations": eng.prefill_invocations - inv0,
            }
        return rows

    on, off = measure(chunks), measure(())
    return [{
        "prompt_len": p_len,
        "ttft_prefill_s": on[p_len]["ttft_s"],
        "ttft_decode_s": off[p_len]["ttft_s"],
        "ttft_speedup": (off[p_len]["ttft_s"] / on[p_len]["ttft_s"]
                         if on[p_len]["ttft_s"] else None),
        "prefill_invocations": on[p_len]["prefill_invocations"],
        "on_prefill_wall_s": on[p_len]["prefill_wall_s"],
        "on_decode_wall_s": on[p_len]["decode_wall_s"],
        "off_wall_s": off[p_len]["wall_s"],
    } for p_len in lens]


def quant_ab(model, params, args) -> dict:
    """The quantization A/B: one seeded greedy workload through a fp32-oracle
    engine and a quantized engine, returning measured bytes, the accuracy
    budget, and the compile pins — the committed-artifact document.

    Both sides run on an fp32 base model regardless of ``--bf16`` (the main
    decomposition bench keeps its own dtype): "nll_fp32" and the byte-reduction
    ratios measure quantization alone against a true fp32 oracle, not a
    baseline whose meaning shifts with an unrelated flag."""
    import numpy as np

    from csed_514_project_distributed_training_using_pytorch_tpu.models import (
        lm as lm_mod,
    )
    from csed_514_project_distributed_training_using_pytorch_tpu.serving import (
        ContinuousBatchingEngine, Request,
    )

    import jax
    import jax.numpy as jnp

    if model.dtype != jnp.float32:
        model = model.clone(dtype=jnp.float32)

    s = args.seq
    rng = np.random.default_rng(11)
    # Prompt-heavy mix (prefill exercised) + short prompts (decode exercised).
    lens = sorted({s // 8, s // 4, s // 2, (3 * s) // 4})
    specs = []
    for i in range(args.ab_requests):
        p_len = int(rng.choice(lens))
        prompt = rng.integers(0, args.vocab, size=p_len).astype(np.int32)
        new = int(rng.integers(args.ab_new_tokens // 2, args.ab_new_tokens + 1))
        specs.append((prompt, new))

    def run_engine(kv_dtype, quant_policy):
        eng = ContinuousBatchingEngine(
            model, params, num_slots=args.ab_slots,
            prefill_chunk_sizes=tuple(
                int(x) for x in args.curve_chunks.split(",") if x),
            kv_dtype=kv_dtype, quant_policy=quant_policy)
        comps = eng.run([Request(prompt=p, max_new_tokens=n, request_id=i)
                         for i, (p, n) in enumerate(specs)])
        return eng, {c.request.request_id: np.asarray(c.tokens) for c in comps}

    eng_a, toks_a = run_engine("model", "off")
    eng_b, toks_b = run_engine(args.ab_kv_dtype, args.ab_quant_policy)

    # Greedy token-match rate vs the fp32 oracle, over GENERATED positions
    # only (the prompt prefix is teacher-forced on both sides). Positionwise
    # agreement; prefix_match additionally reports agreement up to the first
    # divergence (after which conditioning differs by construction).
    agree = total = prefix_agree = 0
    for i, (p, _) in enumerate(specs):
        a, b = toks_a[i][len(p):], toks_b[i][len(p):]
        n = min(len(a), len(b))
        eq = a[:n] == b[:n]
        agree += int(eq.sum())
        total += n
        div = np.nonzero(~eq)[0]
        prefix_agree += int(div[0]) if len(div) else n
    token_match_rate = agree / total if total else None
    prefix_match_rate = prefix_agree / total if total else None

    # NLL delta through the serving decode path, teacher-forced on oracle
    # greedy streams (real model traffic, not random tokens).
    targets = lm_mod.generate(model, params, jax.random.PRNGKey(2),
                              batch=args.ab_nll_batch, temperature=0.0)
    nll_a = float(lm_mod.decode_nll(model, eng_a.params,
                                    jnp.asarray(targets)))
    nll_b = float(lm_mod.decode_nll(model, eng_b.params, jnp.asarray(targets),
                                    kv_dtype=args.ab_kv_dtype))
    acct_a, acct_b = eng_a.byte_accounting(), eng_b.byte_accounting()
    doc = {
        "metric": "quantized-execution A/B (kv %s, weights %s)"
                  % (args.ab_kv_dtype, args.ab_quant_policy),
        "model_dtype": "float32",  # the oracle is pinned fp32 (see docstring)
        "requests": len(specs),
        "prompt_lens": lens,
        "a": {"kv_dtype": "model", "quant_policy": "off", "bytes": acct_a,
              "trace_count": eng_a.trace_count,
              "prefill_trace_counts": dict(eng_a.prefill_trace_counts)},
        "b": {"kv_dtype": args.ab_kv_dtype,
              "quant_policy": args.ab_quant_policy, "bytes": acct_b,
              "trace_count": eng_b.trace_count,
              "prefill_trace_counts": dict(eng_b.prefill_trace_counts)},
        # The two committed ratios: measured decode bytes/token reduction and
        # the slots-per-chip multiplier under the same HBM budget.
        "decode_bytes_per_token_reduction":
            acct_a["decode_bytes_per_token"] / acct_b["decode_bytes_per_token"],
        "kv_bytes_per_slot_reduction":
            acct_a["kv_bytes_per_slot"] / acct_b["kv_bytes_per_slot"],
        "slots_at_budget_ratio":
            (acct_b["slots_at_budget"] / acct_a["slots_at_budget"]
             if acct_a["slots_at_budget"] else None),
        # The accuracy budget, pinned with explicit bounds.
        "token_match_rate": token_match_rate,
        "prefix_match_rate": prefix_match_rate,
        "token_match_bound": args.ab_match_bound,
        "nll_fp32": nll_a,
        "nll_quant": nll_b,
        "nll_delta": nll_b - nll_a,
        "nll_delta_bound": args.ab_nll_bound,
        "one_program_pins": {
            "decode_trace_count_ok":
                eng_a.trace_count == 1 and eng_b.trace_count == 1,
            "prefill_trace_counts_ok": all(
                v <= 1 for e in (eng_a, eng_b)
                for v in e.prefill_trace_counts.values()),
        },
        "accuracy_ok": (token_match_rate is not None
                        and token_match_rate >= args.ab_match_bound
                        and abs(nll_b - nll_a) <= args.ab_nll_bound),
    }
    return doc


def paged_ab(model, params, args) -> dict:
    """The paged-KV A/B (``bench_results/paged_kv_cpu/``): one seeded mixed
    workload — short interactive requests (~32 total tokens) interleaved with
    near-``seq_len`` prompts — through a contiguous-oracle engine (A) and a
    paged engine (B), reporting (1) greedy token identity (the bitwise
    contract the paged adapters are built on); (2) byte-true residency:
    contiguous charges every slot the full ``[S]`` planes, paged charges the
    page span each request actually reserved, so slots-at-HBM-budget is
    measured from THIS workload's page costs, not a dtype formula; (3) the
    long-prompt latency tails (TTFT/TPOT p50/p95 per side) — the paged layout
    must buy capacity without taxing the requests that DO use full context;
    (4) the compile pins and the pool's own ledger (allocs/frees/refusals)."""
    import time as _time

    import numpy as np

    from csed_514_project_distributed_training_using_pytorch_tpu.serving import (
        ContinuousBatchingEngine, Request,
    )
    from csed_514_project_distributed_training_using_pytorch_tpu.serving.pagepool import (
        pages_for,
    )

    s = args.seq
    chunks = tuple(int(x) for x in args.curve_chunks.split(",") if x)
    rng = np.random.default_rng(13)
    long_len = max(s - args.paged_new_tokens - 1, s // 2)
    specs = []      # (kind, prompt, max_new) — shorts with longs interleaved
    for i in range(args.paged_requests):
        p_len = int(rng.integers(8, 24))
        new = max(32 - p_len + int(rng.integers(0, 8)), 1)
        specs.append(("short",
                      rng.integers(0, args.vocab, size=p_len).astype(np.int32),
                      new))
        if i % max(args.paged_requests // args.paged_long_requests, 1) == 0 \
                and sum(k == "long" for k, _, _ in specs) \
                < args.paged_long_requests:
            specs.append((
                "long",
                rng.integers(0, args.vocab, size=long_len).astype(np.int32),
                args.paged_new_tokens))
    warm = rng.integers(0, args.vocab, size=s - 2).astype(np.int32)

    def run_engine(**layout_kw):
        eng = ContinuousBatchingEngine(
            model, params, num_slots=args.paged_slots,
            prefill_chunk_sizes=chunks, **layout_kw)
        # Compile every chunk size + the decode program off the clock (one
        # request per size plans as exactly one chunk), then wipe the ledger.
        for c in eng.prefill_chunk_sizes:
            eng.run([Request(prompt=warm[:min(c, s - 1)], max_new_tokens=1)])
        eng.run([Request(prompt=warm, max_new_tokens=2)])
        eng.reset_stats()
        reqs = [Request(prompt=p, max_new_tokens=n, request_id=i)
                for i, (_, p, n) in enumerate(specs)]
        t0 = _time.monotonic()
        comps = eng.run(reqs)
        wall = _time.monotonic() - t0
        return eng, {c.request.request_id: c for c in comps}, wall

    eng_a, comps_a, wall_a = run_engine()
    eng_b, comps_b, wall_b = run_engine(kv_layout="paged",
                                        page_size=args.paged_page_size)

    identical = all(
        np.array_equal(comps_a[i].tokens, comps_b[i].tokens)
        for i in range(len(specs)))

    def tails(comps, kind):
        rows = [comps[i] for i, (k, _, _) in enumerate(specs) if k == kind]
        out = {}
        for field in ("ttft_s", "tpot_s"):
            vals = [getattr(c, field) for c in rows
                    if getattr(c, field) is not None]
            out[field] = ({"p50": float(np.percentile(vals, 50)),
                           "p95": float(np.percentile(vals, 95))}
                          if vals else None)
        return out

    acct_a, acct_b = eng_a.byte_accounting(), eng_b.byte_accounting()
    ps = eng_b.page_size
    page_bytes = acct_b["page_bytes"]
    # Slots at a fixed HBM budget, measured from THIS workload: contiguous
    # charges kv_bytes_per_slot regardless of context; paged charges the mean
    # page reservation of the mix (each request's ceil(total/ps) pages).
    budget = float(args.paged_hbm_budget
                   or args.paged_slots * acct_a["kv_bytes_per_slot"])
    req_pages = [pages_for(len(p) + n, ps) for _, p, n in specs]
    mean_req_bytes = sum(req_pages) / len(req_pages) * page_bytes
    slots_a = int(budget // acct_a["kv_bytes_per_slot"])
    slots_b = int(budget // mean_req_bytes)
    t_a, t_b = tails(comps_a, "long"), tails(comps_b, "long")
    ttft_ratio = (t_b["ttft_s"]["p95"] / t_a["ttft_s"]["p95"]
                  if t_a.get("ttft_s") and t_b.get("ttft_s")
                  and t_a["ttft_s"]["p95"] else None)
    gen_tokens = sum(c.new_tokens for c in comps_a.values())
    doc = {
        "metric": "paged-KV A/B (page_size %d, %d short + %d long requests)"
                  % (ps, sum(k == "short" for k, _, _ in specs),
                     sum(k == "long" for k, _, _ in specs)),
        "requests": len(specs),
        "seq_len": s,
        "long_prompt_len": long_len,
        "token_identical": bool(identical),
        "a": {"kv_layout": "contiguous", "bytes": acct_a, "wall_s": wall_a,
              "tokens_per_s": gen_tokens / wall_a if wall_a else None,
              "trace_count": eng_a.trace_count,
              "prefill_trace_counts": dict(eng_a.prefill_trace_counts),
              "long": t_a, "short": tails(comps_a, "short")},
        "b": {"kv_layout": "paged", "bytes": acct_b, "wall_s": wall_b,
              "tokens_per_s": gen_tokens / wall_b if wall_b else None,
              "trace_count": eng_b.trace_count,
              "prefill_trace_counts": dict(eng_b.prefill_trace_counts),
              "long": t_b, "short": tails(comps_b, "short"),
              "kv_pages": eng_b.page_stats()},
        # The committed capacity claim: how many of THIS mix's requests fit
        # the same HBM budget under each layout.
        "hbm_budget_bytes": budget,
        "mean_request_pages": sum(req_pages) / len(req_pages),
        "page_bytes": page_bytes,
        "slots_at_budget_contiguous": slots_a,
        "slots_at_budget_paged": slots_b,
        "slots_at_budget_ratio": slots_b / slots_a if slots_a else None,
        "slots_ratio_bound": args.paged_slots_bound,
        "long_ttft_p95_ratio": ttft_ratio,
        "long_ttft_bound": args.paged_ttft_bound,
        "capacity_ok": (slots_a > 0
                        and slots_b / slots_a >= args.paged_slots_bound),
        "latency_ok": (ttft_ratio is not None
                       and ttft_ratio <= args.paged_ttft_bound),
        "accounting": ("byte-true: per-slot/page bytes from live buffers; "
                       "page costs from the engine's own pages_for"),
    }
    return doc


def main() -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--vocab", type=int, default=16)
    p.add_argument("--seq", type=int, default=784)
    p.add_argument("--gen-batch", type=int, default=8)
    p.add_argument("--d-model", type=int, default=256)
    p.add_argument("--layers", type=int, default=4)
    p.add_argument("--heads", type=int, default=8)
    p.add_argument("--bf16", action=argparse.BooleanOptionalAction, default=True)
    p.add_argument("--ttft-curve", action=argparse.BooleanOptionalAction,
                   default=False,
                   help="add the serving TTFT-vs-prompt-length curve, chunked "
                        "prefill on vs off, with the prefill/decode wall split")
    p.add_argument("--curve-prompt-lens", default="64,256,512,768",
                   help="prompt lengths for --ttft-curve (clipped to < --seq)")
    p.add_argument("--curve-chunks", default="32,128,512",
                   help="prefill chunk-size set for the ON side of the curve")
    p.add_argument("--curve-new-tokens", type=int, default=8)
    p.add_argument("--checkpoint", default="",
                   help="TrainState or params msgpack from train.lm — real "
                        "weights for the accuracy-budget side of --quant-ab "
                        "(default: seeded random init)")
    p.add_argument("--quant-ab", action=argparse.BooleanOptionalAction,
                   default=False,
                   help="run the quantized-execution A/B (fp32 oracle vs "
                        "--ab-kv-dtype/--ab-quant-policy engine): measured "
                        "bytes, accuracy budget, compile pins")
    p.add_argument("--ab-kv-dtype", default="int8",
                   choices=("fp32", "bf16", "int8", "fp8"))
    p.add_argument("--ab-quant-policy", default="w8",
                   choices=("off", "w8", "w8a8"))
    p.add_argument("--ab-requests", type=int, default=8)
    p.add_argument("--ab-new-tokens", type=int, default=16)
    p.add_argument("--ab-slots", type=int, default=4)
    p.add_argument("--ab-nll-batch", type=int, default=4)
    p.add_argument("--ab-match-bound", type=float, default=0.98,
                   help="min greedy token-match rate vs the fp32 oracle "
                        "(the documented accuracy budget)")
    p.add_argument("--ab-nll-bound", type=float, default=0.05,
                   help="max |NLL delta| through the quantized decode path")
    p.add_argument("--paged-ab", action=argparse.BooleanOptionalAction,
                   default=False,
                   help="run the paged-KV A/B (contiguous oracle vs "
                        "kv_layout='paged'): token identity, measured "
                        "slots-at-HBM-budget on a mixed short/long workload, "
                        "long-prompt TTFT/TPOT tails, compile pins")
    p.add_argument("--paged-page-size", type=int, default=64)
    p.add_argument("--paged-requests", type=int, default=12,
                   help="short (~32 total tokens) requests in the mix")
    p.add_argument("--paged-long-requests", type=int, default=4,
                   help="near-seq_len prompts interleaved into the mix")
    p.add_argument("--paged-new-tokens", type=int, default=8,
                   help="generated tokens per long request")
    p.add_argument("--paged-slots", type=int, default=4)
    p.add_argument("--paged-hbm-budget", type=float, default=0.0,
                   help="HBM budget (bytes) for the slots-at-budget claim; "
                        "0 = paged_slots contiguous slots' worth")
    p.add_argument("--paged-slots-bound", type=float, default=2.0,
                   help="min paged/contiguous slots-at-budget ratio")
    p.add_argument("--paged-ttft-bound", type=float, default=1.25,
                   help="max long-prompt p95 TTFT ratio (paged/contiguous)")
    p.add_argument("--out", default=None)
    args = p.parse_args()

    import jax
    import jax.numpy as jnp
    from jax import lax

    from csed_514_project_distributed_training_using_pytorch_tpu.models import lm as lm_mod
    from csed_514_project_distributed_training_using_pytorch_tpu.utils.benchmarks import (
        chained_diff_time, peak_hbm_bytes,
    )

    model = lm_mod.TransformerLM(
        vocab_size=args.vocab + 1, seq_len=args.seq, embed_dim=args.d_model,
        num_layers=args.layers, num_heads=args.heads,
        dtype=jnp.bfloat16 if args.bf16 else jnp.float32)
    params = model.init({"params": jax.random.PRNGKey(0)},
                        jnp.zeros((1, args.seq), jnp.int32))["params"]
    if args.checkpoint:
        from csed_514_project_distributed_training_using_pytorch_tpu.utils import (
            checkpoint as ckpt_mod,
        )
        params = ckpt_mod.load_params_or_state(args.checkpoint, params)

    # --- 3. ops per token: the optimized HLO of ONE decode step ---------------
    cache = lm_mod.init_cache(model, args.gen_batch)
    tok = jnp.zeros((args.gen_batch,), jnp.int32)

    def one_step(params, cache, tok):
        cache, logp = lm_mod.decode_step(model, params, cache, tok,
                                         jnp.int32(0), prefix_len=128)
        return cache, logp

    compiled = jax.jit(one_step).lower(params, cache, tok).compile()
    hlo = compiled.as_text()
    # Executable ops = instructions in ENTRY whose opcode launches work on the
    # TensorCore: fusions, custom-calls, copies, convolutions/dots that escaped
    # fusion. Parameter/tuple plumbing is free.
    entry = hlo.split("ENTRY")[-1]
    launched = re.findall(
        r"= \S+ (fusion|custom-call|copy|convolution|dot|all-reduce|"
        r"dynamic-slice|dynamic-update-slice|reduce|transpose|select-and-scatter)",
        entry)
    ops_per_token = len(launched)
    op_kinds = {}
    for kind in launched:
        op_kinds[kind] = op_kinds.get(kind, 0) + 1

    # --- 1. measured per-token seconds (bench_lm's protocol) ------------------
    def gen_chain(n):
        def body(k, _):
            ids = lm_mod.generate(model, params, k, batch=args.gen_batch,
                                  temperature=1.0)
            return jax.random.fold_in(k, jnp.sum(ids)), ()

        def run(k):
            return lax.scan(body, k, None, length=n)[0]

        return jax.jit(run)

    def synced(n):
        compiled = gen_chain(n)
        return lambda: jax.device_get(compiled(jax.random.PRNGKey(3)))

    per_gen, (n1, t1), (n2, t2), converged = chained_diff_time(
        synced, n1=1, grow=4, max_n=64)
    t_token = per_gen / args.seq

    # --- 2. HBM roofline per token (byte-TRUE accounting) ---------------------
    # Bytes come from the ACTUAL buffers, not closed-form dtype assumptions:
    # one cached position's bytes = the real per-slot cache (planes AND any
    # scale planes, at their real itemsize) over seq_len; weights = the real
    # params tree. A quantized run's roofline denominator therefore shrinks
    # exactly as far as its buffers did — the accounting rule the quantized
    # A/B below relies on.
    from csed_514_project_distributed_training_using_pytorch_tpu.ops import (
        quant as quant_ops,
    )

    s = args.seq
    # average static prefix read per step under the segmented scan
    seg = lm_mod.DECODE_SEGMENT
    nseg = -(-s // seg)
    avg_prefix = sum(min((j + 1) * seg, s) * seg for j in range(nseg)) / s
    row_bytes = quant_ops.tree_bytes(lm_mod.init_cache(model, 1)) / s
    cache_bytes = row_bytes * avg_prefix
    weight_bytes = quant_ops.tree_bytes(params)
    bytes_per_token = cache_bytes + weight_bytes / args.gen_batch
    dev = jax.devices()[0]
    hbm = (peak_hbm_bytes(getattr(dev, "device_kind", ""))
           if dev.platform == "tpu" else None)
    t_roofline = (args.gen_batch * bytes_per_token / hbm) if hbm else None

    residual = (t_token - t_roofline) if t_roofline else None
    doc = {
        "metric": "LM decode per-token decomposition (d=%d)" % args.d_model,
        "platform": dev.platform,
        "device_kind": getattr(dev, "device_kind", dev.platform),
        "d_model": args.d_model, "layers": args.layers, "heads": args.heads,
        "seq": s, "decode_batch": args.gen_batch,
        "tokens_per_s": round(args.gen_batch * s / per_gen, 1),
        "t_token_s": t_token, "chain_converged": converged,
        "ops_per_token": ops_per_token, "op_kinds": op_kinds,
        "t_roofline_s": t_roofline,
        "hbm_roofline_frac": (round(t_roofline / t_token, 4)
                              if t_roofline else None),
        "residual_s": residual,
        "per_op_overhead_us": (round(1e6 * residual / ops_per_token, 3)
                               if residual is not None else None),
        "attribution": ("residual / ops_per_token is the device's per-op launch "
                        "floor; the fixed per-dispatch host cost is cancelled by "
                        "the chained two-point protocol"),
        "accounting": "byte-true: cache/weight bytes summed from live buffers",
    }
    if args.ttft_curve:
        doc["ttft_curve"] = ttft_curve(model, params, args)
    if args.quant_ab:
        doc["quant_ab"] = quant_ab(model, params, args)
    if args.paged_ab:
        doc["paged_ab"] = paged_ab(model, params, args)
    print(json.dumps(doc))
    if args.out:
        with open(args.out, "w") as f:
            json.dump(doc, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
