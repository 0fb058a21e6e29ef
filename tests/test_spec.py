"""Speculative decoding (serving/spec/ + models.lm.verify_chunk): the contracts.

The subsystem's three invariants, pinned tier-1 on tiny models:

1. **Greedy identity** — propose->verify->accept emits the EXACT token stream
   of sequential ``models.lm.generate`` for every request, across
   MHA/GQA/windowed/RoPE configs, recycled slots, and drafters that miss
   mid-stream (a wrong draft costs acceptance, never correctness — every
   verify row's correction IS the target argmax).
2. **One program** — serving any request mix traces the verify program at most
   once per configured width (``verify_trace_counts``), the DECODE program
   zero times (spec mode replaces it), and the draft LM's own step/prefill
   programs at most once each.
3. **Distribution preservation** — at temperature > 0 the rejection-sampling
   rule leaves the emitted distribution within a small total-variation
   distance of the non-speculative sampler's (the quant suite's bound style).

Plus the spec x int8-KV x prefix-cache composition pin, the accept-stats
telemetry schema (``"spec"`` events + ``serve_summary`` spec/invocation
fields), the draft/verify trace-segment split summing to e2e, and the loadgen
flag plumbing.
"""

import importlib.util
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from csed_514_project_distributed_training_using_pytorch_tpu.models import lm
from csed_514_project_distributed_training_using_pytorch_tpu.serving import (
    ContinuousBatchingEngine,
    Request,
    SamplingParams,
    Server,
)
from csed_514_project_distributed_training_using_pytorch_tpu.serving.spec import (
    Drafter,
    DraftLMDrafter,
    NGramDrafter,
    greedy_chunk_plan,
)
from csed_514_project_distributed_training_using_pytorch_tpu.utils.metrics import (
    load_metrics_jsonl,
)

_REPO = os.path.join(os.path.dirname(__file__), os.pardir)

SMALL = dict(vocab_size=9, seq_len=16, embed_dim=32, num_layers=2, num_heads=4)


def _model(**kw):
    return lm.TransformerLM(**{**SMALL, **kw})


def _params(model, seed=0):
    ids = jnp.zeros((1, model.seq_len), jnp.int32)
    return model.init({"params": jax.random.PRNGKey(seed)}, ids)["params"]


def _mixed_requests(model, n, seed=0, temperature=0.0):
    rng = np.random.default_rng(seed)
    sampling = SamplingParams(temperature=temperature)
    reqs = []
    for i in range(n):
        plen = int(rng.integers(0, model.seq_len // 2))
        reqs.append(Request(
            prompt=rng.integers(0, model.vocab_size - 1,
                                size=plen).astype(np.int32),
            max_new_tokens=int(rng.integers(1, model.seq_len)),
            sampling=sampling, request_id=i))
    return reqs


def _sequential_reference(model, params, req):
    p = len(req.prompt)
    total = min(p + req.max_new_tokens, model.seq_len)
    padded = np.zeros((1, model.seq_len), np.int32)
    padded[0, :p] = req.prompt
    out = lm.generate(model, params, jax.random.PRNGKey(0), batch=1,
                      temperature=0.0, prompt=jnp.asarray(padded), prompt_len=p)
    return np.asarray(out)[0, :total]


class _ConstDrafter(Drafter):
    """Always proposes ``k`` copies of one fixed token — the controlled-miss
    drafter: acceptance happens exactly where the target agrees, and every
    disagreement exercises the correction path."""

    name = "const"

    def __init__(self, token: int):
        self.token = int(token)

    def propose(self, slot, tokens, last, k):
        return np.full((k,), self.token, np.int32)


# -----------------------------------------------------------------------------------------
# Greedy identity + the one-program contract
# -----------------------------------------------------------------------------------------


@pytest.mark.parametrize("cfg,n_req", [
    (dict(), 8),                                  # MHA, the full 8-request mix
    (dict(num_kv_heads=2), 4),                    # GQA (smaller per-slot cache)
    (dict(attention_window=5), 4),                # sliding-window verify mask
    (dict(rope=True), 4),                         # per-position rotary in-chunk
], ids=["mha", "gqa", "window", "rope"])
def test_spec_greedy_identity_with_sequential_generate(cfg, n_req):
    """Acceptance: n-gram speculative decode is token-identical to sequential
    ``generate`` per request — through FEWER slots than requests (slots are
    freed and recycled mid-stream), with the verify program compiled exactly
    once and the plain decode program never traced."""
    model = _model(**cfg)
    params = _params(model)
    reqs = _mixed_requests(model, n_req, seed=7)
    engine = ContinuousBatchingEngine(model, params, num_slots=3,
                                      spec="ngram", spec_k=3)
    comps = {c.request.request_id: c for c in engine.run(reqs)}
    assert engine.verify_trace_counts == {3: 1}
    assert engine.trace_count == 0            # decode program never traced
    assert sorted(comps) == list(range(n_req))
    for req in reqs:
        ref = _sequential_reference(model, params, req)
        got = comps[req.request_id]
        assert got.ok and got.prompt_len == len(req.prompt)
        np.testing.assert_array_equal(got.tokens, ref)


def test_spec_identity_survives_mid_stream_drafter_misses():
    """A drafter that is wrong most of the time (constant-token proposals)
    still yields token-identical output: a miss burns speculation, never
    correctness — and a verify step with zero accepted drafts degenerates to
    plain one-token decode through the same program."""
    model = _model()
    params = _params(model)
    reqs = _mixed_requests(model, 6, seed=3)
    engine = ContinuousBatchingEngine(model, params, num_slots=2, spec="const",
                                      spec_k=4, drafter=_ConstDrafter(2))
    comps = {c.request.request_id: c for c in engine.run(reqs)}
    assert engine.verify_trace_counts == {4: 1}
    st = engine.spec_stats()
    assert st["proposed"] > 0
    # The controlled-miss drafter cannot be right every time on this stream.
    assert st["accepted"] < st["proposed"]
    for req in reqs:
        np.testing.assert_array_equal(comps[req.request_id].tokens,
                                      _sequential_reference(model, params, req))


def test_spec_draft_lm_identity_and_one_program_pins():
    """The draft-LM drafter with the TARGET's own params (the perfect-drafter
    limit): high acceptance, token-identical output, and every program —
    verify, draft step, draft prefill — traced at most once."""
    model = _model()
    params = _params(model)
    reqs = _mixed_requests(model, 6, seed=11)
    drafter = DraftLMDrafter(model, params, chunk_sizes=(8,))
    engine = ContinuousBatchingEngine(model, params, num_slots=3,
                                      spec="draft-lm", spec_k=3,
                                      drafter=drafter)
    comps = {c.request.request_id: c for c in engine.run(reqs)}
    for req in reqs:
        np.testing.assert_array_equal(comps[req.request_id].tokens,
                                      _sequential_reference(model, params, req))
    st = engine.spec_stats()
    assert st["acceptance_rate"] > 0.5        # the draft IS the target
    assert st["accepted_tokens_per_step"] > 1.5
    assert engine.steps < engine.generated_tokens  # >1 token per invocation
    assert engine.verify_trace_counts == {3: 1}
    assert drafter.step_trace_count == 1
    assert all(v <= 1 for v in drafter.prefill_trace_counts.values())
    assert engine.trace_count == 0


def test_spec_draft_lm_rejects_mismatched_tokenizer():
    model = _model()
    other = _model(vocab_size=12)
    drafter = DraftLMDrafter(other, _params(other), chunk_sizes=(8,))
    with pytest.raises(ValueError, match="vocab"):
        ContinuousBatchingEngine(model, _params(model), num_slots=2,
                                 spec="draft-lm", spec_k=2, drafter=drafter)


def test_spec_engine_ctor_validation():
    model = _model()
    params = _params(model)
    with pytest.raises(ValueError, match="unknown spec mode"):
        ContinuousBatchingEngine(model, params, num_slots=1, spec="turbo")
    with pytest.raises(ValueError, match="DraftLMDrafter"):
        ContinuousBatchingEngine(model, params, num_slots=1, spec="draft-lm")
    with pytest.raises(ValueError, match="spec_k"):
        ContinuousBatchingEngine(model, params, num_slots=1, spec="ngram",
                                 spec_k=0)
    with pytest.raises(ValueError, match="chunked-prefill"):
        ContinuousBatchingEngine(model, params, num_slots=1, spec="ngram",
                                 prefill_chunk_sizes=())
    # Spec and drafter must AGREE: an A/B harness toggling spec with a
    # drafter held fixed can never silently run speculation on both sides.
    with pytest.raises(ValueError, match="never enabled implicitly"):
        ContinuousBatchingEngine(model, params, num_slots=1, spec="off",
                                 drafter=_ConstDrafter(1))
    with pytest.raises(ValueError, match="does not match"):
        ContinuousBatchingEngine(model, params, num_slots=1, spec="ngram",
                                 drafter=_ConstDrafter(1))


# -----------------------------------------------------------------------------------------
# Rejection sampling at temperature > 0: distribution-level budget
# -----------------------------------------------------------------------------------------


def test_spec_rejection_sampling_total_variation_bound():
    """Distribution preservation: with a drafter in play on the very first
    generated token, temperature-1.0 speculative sampling draws that token from
    the model's own next-token distribution, as the non-speculative sampler
    does — the rejection rule (accept d w.p. p(d), else resample from p with d
    masked) IS the target distribution, so only RNG scheduling differs.

    Each sampler's ``n`` draws are held against the exact distribution ``p``
    (every request has the same prompt), not against each other: two empirical
    distributions of 64 draws over 8 tokens lie 0.19 apart on average, which the
    earlier bound of 0.15 between them did not allow. The bound is the 99.9th
    percentile of the total-variation distance between ``n`` draws of ``p`` and
    ``p`` itself, over 4,000 seeded multinomial draws (0.130 at n = 256, mean
    0.066; an always-accept rule would read 1 - p(3) = 0.89)."""
    model = _model()
    params = _params(model)
    n = 256
    sampling = SamplingParams(temperature=1.0)
    reqs = [Request(prompt=np.asarray([1, 2], np.int32), max_new_tokens=2,
                    sampling=sampling, request_id=i) for i in range(n)]

    def first_tokens(**kw):
        eng = ContinuousBatchingEngine(model, params, num_slots=4, seed=123,
                                       **kw)
        outs = {c.request.request_id: c for c in eng.run(list(reqs))}
        # tokens = [prompt, first sampled, second sampled]
        return np.array([int(outs[i].tokens[2]) for i in range(n)]), eng

    a, _ = first_tokens()
    b, eng = first_tokens(spec="const", spec_k=2, drafter=_ConstDrafter(3))
    assert eng.spec_stats()["proposed"] > 0   # drafts were actually in play
    # The target: the float32 forward's distribution after the prompt, without the
    # BOS id, which the sampler masks.
    ids = np.zeros((1, model.seq_len), np.int32)
    ids[0, :2] = reqs[0].prompt
    logp = model.apply({"params": params}, model.shift_right(jnp.asarray(ids)))
    p = np.exp(np.asarray(logp, np.float64)[0, 2, :-1])
    p /= p.sum()
    draws = np.random.default_rng(0).multinomial(n, p, size=4000) / n
    bound = float(np.quantile(0.5 * np.abs(draws - p).sum(axis=1), 0.999))
    for name, tokens in (("plain", a), ("speculative", b)):
        tv = 0.5 * float(np.abs(np.bincount(tokens, minlength=p.size) / n - p).sum())
        assert tv <= bound, f"{name}: total-variation distance {tv:.3f} > {bound:.3f}"


# -----------------------------------------------------------------------------------------
# Composition: spec x int8 KV x prefix cache
# -----------------------------------------------------------------------------------------


def test_spec_composes_with_int8_kv_and_prefix_cache():
    """Verify-written rows carry the identical quantize-on-write rounding as
    the per-token path, so an int8+spec engine is token-identical to an int8
    non-spec engine — with the prefix cache live on both (shared-prefix
    prompts force hits) and every one-program pin holding."""
    model = _model()
    params = _params(model)
    rng = np.random.default_rng(5)
    shared = rng.integers(0, model.vocab_size - 1, size=6).astype(np.int32)
    reqs = []
    for i in range(6):
        extra = rng.integers(0, model.vocab_size - 1,
                             size=int(rng.integers(0, 4))).astype(np.int32)
        reqs.append(Request(prompt=np.concatenate([shared, extra]),
                            max_new_tokens=int(rng.integers(1, 6)),
                            request_id=i))

    def run(**kw):
        eng = ContinuousBatchingEngine(
            model, params, num_slots=2, kv_dtype="int8", quant_policy="w8",
            prefix_cache_entries=4, prefill_chunk_sizes=(4,), **kw)
        return eng, {c.request.request_id: c for c in eng.run(list(reqs))}

    eng_a, toks_a = run()
    eng_b, toks_b = run(spec="ngram", spec_k=3)
    for i in toks_a:
        np.testing.assert_array_equal(toks_a[i].tokens, toks_b[i].tokens)
    assert eng_b.prefix_cache.stats()["hits"] > 0   # cache engaged under spec
    assert eng_b.verify_trace_counts == {3: 1}
    assert all(v <= 1 for v in eng_b.prefill_trace_counts.values())
    assert eng_b.trace_count == 0


# -----------------------------------------------------------------------------------------
# Drafters
# -----------------------------------------------------------------------------------------


def test_ngram_drafter_prompt_lookup():
    d = NGramDrafter(max_n=3, min_n=1)
    # Trailing [5, 6] occurred earlier, followed by 7, 8, 1 — propose those.
    stream = [1, 5, 6, 7, 8, 1, 3, 5, 6]
    np.testing.assert_array_equal(d.propose(0, stream, 6, 3), [7, 8, 1])
    # Most RECENT occurrence wins: trailing [2] matched at its later site.
    stream = [2, 9, 4, 2, 8, 2]
    np.testing.assert_array_equal(d.propose(0, stream, 2, 2), [8, 2])
    # No history / no match: no proposal (degenerates to plain decode).
    assert d.propose(0, [], 0, 4).size == 0
    assert d.propose(0, [1, 2, 3], 3, 4).size == 0
    with pytest.raises(ValueError, match="min_n"):
        NGramDrafter(max_n=2, min_n=3)


def test_greedy_chunk_plan_owner():
    """engine.plan_prefill and the draft LM's install share the one plan
    rule: a single configured size c costs exactly ceil(n / c) chunks."""
    assert greedy_chunk_plan((4,), 0, 10) == [(0, 4, 4), (4, 4, 4), (8, 2, 4)]
    assert greedy_chunk_plan((4, 8), 0, 13) == [(0, 8, 8), (8, 4, 4),
                                                (12, 1, 4)]
    model = _model()
    eng = ContinuousBatchingEngine(model, _params(model), num_slots=1,
                                   prefill_chunk_sizes=(4, 8))
    assert eng.plan_prefill(0, 13) == greedy_chunk_plan((4, 8), 0, 13)


# -----------------------------------------------------------------------------------------
# Accounting + telemetry schema
# -----------------------------------------------------------------------------------------


def test_serve_summary_separates_invocations_from_tokens(tmp_path):
    """The multi-token-step accounting fix: serve_summary reports decode
    PROGRAM INVOCATIONS and GENERATED TOKENS as separate counters (and the
    per-step "spec" events carry the accept stats), so tokens/s math stays
    honest when K>1 tokens land per program."""
    model = _model()
    params = _params(model)
    path = str(tmp_path / "serve.jsonl")
    drafter = DraftLMDrafter(model, params, chunk_sizes=(8,))
    engine = ContinuousBatchingEngine(model, params, num_slots=2,
                                      spec="draft-lm", spec_k=3,
                                      drafter=drafter)
    server = Server(engine, telemetry=path).start()
    futs = [server.submit(np.asarray([1, 2, 3], np.int32), max_new_tokens=8)
            for _ in range(4)]
    comps = [f.result(timeout=60) for f in futs]
    server.stop()
    assert all(c.ok for c in comps)
    rows = load_metrics_jsonl(path)
    config = next(r for r in rows if r["event"] == "serve_config")
    assert config["spec"] == "draft-lm" and config["spec_k"] == 3
    specs = [r for r in rows if r["event"] == "spec"]
    assert specs, "no per-step spec accept-stats events"
    assert all(r["emitted"] >= r["active"] for r in specs)
    summary = next(r for r in rows if r["event"] == "serve_summary")
    gen = summary["generated_tokens"]
    inv = summary["decode_invocations"]
    assert gen == sum(c.new_tokens for c in comps)
    assert inv == engine.steps and inv < gen       # >1 token/program
    assert summary["tokens_per_invocation"] == pytest.approx(gen / inv)
    sp = summary["spec"]
    assert sp["mode"] == "draft-lm" and sp["k"] == 3
    assert sp["accepted_tokens_per_step"] > 1.0
    # Per-step event totals reconcile with the engine ledger.
    assert sum(r["emitted"] for r in specs) == gen
    assert sum(r["accepted"] for r in specs) == sp["accepted"]


def test_report_renders_spec_rows_a_vs_b(tmp_path, capsys):
    """tools/telemetry_report renders the spec line and the accepted-tok/step
    / acceptance-rate A-vs-B rows from a spec-off vs spec-on pair."""
    spec = importlib.util.spec_from_file_location(
        "telemetry_report", os.path.join(_REPO, "tools",
                                         "telemetry_report.py"))
    report = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(report)
    model = _model()
    params = _params(model)
    paths = []
    for name, kw in (("a", {}), ("b", dict(spec="ngram", spec_k=3))):
        path = str(tmp_path / f"{name}.jsonl")
        engine = ContinuousBatchingEngine(model, params, num_slots=2, **kw)
        server = Server(engine, telemetry=path).start()
        futs = [server.submit(np.asarray([1, 1, 1, 1], np.int32),
                              max_new_tokens=6) for _ in range(3)]
        for f in futs:
            f.result(timeout=60)
        server.stop()
        paths.append(path)
    capsys.readouterr()
    assert report.main(paths) == 0
    out = capsys.readouterr().out
    assert "spec: ngram k=3" in out
    assert "accepted tok/step" in out and "acceptance rate" in out
    assert "decode invocations" in out


# -----------------------------------------------------------------------------------------
# Tracing: draft/verify child segments of the decode window
# -----------------------------------------------------------------------------------------


def test_trace_decode_span_splits_into_draft_and_verify(tmp_path):
    """Traced spec runs emit per-tick draft/verify spans inside the decode
    window; trace_breakdown charges them to their own exclusive segments and
    the segments still sum to e2e (overhead absorbs the rest). The Chrome
    export stays schema-valid with the new span names."""
    from csed_514_project_distributed_training_using_pytorch_tpu.utils import (
        trace,
    )

    model = _model()
    params = _params(model)
    trace_path = str(tmp_path / "server.jsonl")
    engine = ContinuousBatchingEngine(model, params, num_slots=2,
                                      spec="ngram", spec_k=3)
    server = Server(engine, trace=trace_path).start()
    futs = [server.submit(np.asarray([2, 2, 2, 2, 2], np.int32),
                          max_new_tokens=8) for _ in range(3)]
    for f in futs:
        f.result(timeout=60)
    server.stop()
    spans, _ = trace.read_spans([trace_path])
    names = {s["name"] for s in spans}
    assert {"draft", "verify", "decode", "resolve"} <= names
    summary = trace.summarize_traces(spans)
    assert summary["orphans"] == 0
    assert "draft" in summary["segments"] and "verify" in summary["segments"]
    for tid, down in summary["by_trace"].items():
        seg = down["segments"]
        assert seg["draft"] > 0 and seg["verify"] > 0
        # Exclusive accounting: the segments (overhead included) sum to e2e.
        assert sum(seg.values()) == pytest.approx(down["e2e_s"], abs=1e-6)
        # draft+verify are carved OUT of the decode window, never on top.
        decode_spans = [s for s in spans if s["trace_id"] == tid
                        and s["name"] == "decode"]
        dur = sum(s["dur_s"] for s in decode_spans)
        total = (seg["draft"] + seg["verify"] + seg["decode_first"]
                 + seg["decode_tail"])
        assert total == pytest.approx(dur, abs=2e-3)
    doc = trace.chrome_trace(spans)
    assert trace.validate_chrome(doc) == []


# -----------------------------------------------------------------------------------------
# Loadgen plumbing
# -----------------------------------------------------------------------------------------


def _load_tool(name):
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(_REPO, "tools", f"{name}.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_loadgen_spec_flags_reach_replica_command_and_summary(tmp_path, capsys):
    """--spec/--spec-k plumb through: the replica argv mirrors them (fleet
    mode) and an in-process run lands spec stats + invocation counters in
    --summary-json."""
    loadgen = _load_tool("serve_loadgen")
    parser_args = [
        "--seq-len", "16", "--embed-dim", "16", "--num-layers", "1",
        "--num-heads", "2", "--num-levels", "8", "--max-new-tokens", "6",
        "--prompt-lens", "0,3,6", "--seed", "0",
        "--spec", "ngram", "--spec-k", "3",
    ]
    summary = tmp_path / "spec_on.json"
    rc = loadgen.main(["--requests", "6", "--mode", "closed",
                       "--concurrency", "2", "--num-slots", "2",
                       "--summary-json", str(summary), *parser_args])
    assert rc == 0
    out = capsys.readouterr().out
    assert "spec: ngram k=3" in out
    doc = json.loads(summary.read_text())
    assert doc["spec"] == "ngram" and doc["spec_k"] == 3
    assert doc["verify_compilations"] == {"3": 1}
    assert doc["decode_compilations"] == 0
    assert doc["spec_stats"]["mode"] == "ngram"
    assert doc["generated_tokens"] == doc["new_tokens"]
    assert doc["decode_invocations"] <= doc["generated_tokens"]

    # Fleet mode mirrors the flags into the replica command verbatim.
    import argparse as _ap

    ns = _ap.Namespace(
        echo=False, seq_len=16, num_levels=8, embed_dim=16, num_layers=1,
        num_heads=2, kv_heads=0, attention_window=0, seed=0, num_slots=2,
        max_pending=4, timeout_s=0.0, prefill_chunks="4", prefill_budget=1,
        prefix_cache=0, kv_dtype="model", quant_policy="off", warmup=0,
        rope=False, checkpoint="", spec="draft-lm", spec_k=5, draft_layers=1,
        draft_embed_dim=16, draft_heads=2, draft_checkpoint="d.msgpack")
    cmd = loadgen.build_replica_command(ns)
    joined = " ".join(cmd)
    assert "--spec draft-lm" in joined and "--spec-k 5" in joined
    assert "--draft-layers 1" in joined
    assert "--draft-checkpoint d.msgpack" in joined
