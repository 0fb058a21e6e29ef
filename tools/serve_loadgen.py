"""Load generator for the serving stack: open/closed loop, chat sessions, fleets.

Drives ``serving.Server`` (one in-process engine) or — with ``--replicas N`` —
``serving.Router`` (a process-per-replica fleet over ``serving/replica.py``)
with a reproducible synthetic workload and leaves a telemetry JSONL behind for
``tools/telemetry_report.py``:

- **open loop** (``--mode open``): requests arrive on a Poisson process at
  ``--rate`` req/s regardless of completions — the latency-under-load probe (an
  overloaded server shows up as queue-wait/TTFT growth, and past ``--max-pending``
  as rejected requests, i.e. backpressure);
- **closed loop** (``--mode closed``): ``--concurrency`` clients each keep exactly
  one request in flight — the throughput probe (tokens/s at a fixed offered
  parallelism);
- **chat** (``--scenario chat``): ``--sessions`` concurrent multi-turn sessions,
  each turn resubmitting the prior context plus the model's reply plus a few
  fresh "user" tokens — the workload where prefix reuse actually pays, because
  every turn's prompt extends the previous one. With ``--replicas N`` this is
  the prefix-affinity A/B: ``--affinity on`` routes a session's turns to the
  replica whose ``prefix_cache`` holds its history, ``--affinity off`` is the
  least-loaded baseline (compare the summaries' prefix-cache hit rates).

The prompt/length mix is sampled per request from ``--prompt-lens`` and
``[1, --max-new-tokens]`` under a seeded RNG, so an A-vs-B pair of runs offers
byte-identical workloads. ``--prompt-dist long`` swaps in a long-prompt mixture
(half to three-quarters of ``seq_len``) that actually exercises the chunked
prefill path, and ``--shared-prefix-len N`` gives every prompt the same first
``N`` tokens (the system-prompt pattern the prefix KV cache exists for). Params
come from a training checkpoint (``--checkpoint results/model_lm.ckpt`` — either
a full TrainState or a params-only export) or a seeded random init when omitted
(pure perf mode).

Prefill knobs mirror the engine's: ``--prefill-chunks 32,128,512`` (empty string
= legacy prefill-as-decode — the A/B switch), ``--prefill-budget`` chunks per
engine step, ``--prefix-cache N`` LRU entries. The run summary reports prefill
token throughput and prefix-cache hits alongside decode tokens/s, and
``--summary-json PATH`` writes the whole summary (TTFT/e2e percentiles included)
as one JSON document for committed A-vs-B artifacts.

Usage::

    python tools/serve_loadgen.py --requests 32 --mode open --rate 16 \\
        --num-slots 8 --telemetry results/serve.jsonl
    python tools/serve_loadgen.py --requests 32 --mode closed --concurrency 8 \\
        --checkpoint results/model_lm.ckpt --telemetry results/serve.jsonl
    python tools/serve_loadgen.py --prompt-dist long --prefix-cache 8 \\
        --shared-prefix-len 256 --summary-json results/prefill_on.json
    python tools/serve_loadgen.py --replicas 2 --scenario chat --sessions 8 \\
        --turns 4 --prefix-cache 8 --affinity on --telemetry results/router.jsonl \\
        --summary-json results/chat_affinity_on.json
    python tools/telemetry_report.py results/serve.jsonl
"""

from __future__ import annotations

import argparse
import os
import sys
import threading
import time

# Script-mode import path: ``python tools/serve_loadgen.py`` puts tools/ on
# sys.path, not the repo root the package lives in.
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np  # noqa: E402


def prompt_len_mix(args) -> list[int]:
    """The prompt-length mixture: ``--prompt-lens`` verbatim, or the ``long``
    preset — seq_len/2 .. 3·seq_len/4, the prompt-heavy regime where TTFT is
    dominated by prefill (the benchmark the chunked-prefill path exists for)."""
    if args.prompt_dist == "long":
        s = args.seq_len
        lens = sorted({max(1, s // 2), max(1, (5 * s) // 8),
                       max(1, min(s - 2, (3 * s) // 4))})
    else:
        lens = [int(x) for x in args.prompt_lens.split(",") if x != ""]
    bad = [l for l in lens if not 0 <= l < args.seq_len]
    if bad:
        raise SystemExit(f"prompt lengths outside [0, seq_len): {bad}")
    return lens


def tenant_shares(text: str) -> dict[str, float]:
    """The loadgen-side reading of the ``--tenants`` grammar: tenant names
    plus their ``share=`` traffic fractions (the scheduler ignores ``share`` —
    it is offered-load mix, not service class), normalized to sum to 1.
    Tenants without a share split the remainder equally."""
    shares: dict[str, float] = {}
    for chunk in (text or "").split(";"):
        chunk = chunk.strip()
        if not chunk:
            continue
        name, _, body = chunk.partition(":")
        share = None
        for part in body.split(","):
            key, _, value = part.strip().partition("=")
            if key.strip() == "share":
                share = float(value)
        shares[name.strip()] = share
    named = sum(v for v in shares.values() if v is not None)
    rest = [k for k, v in shares.items() if v is None]
    for k in rest:
        shares[k] = max(0.0, 1.0 - named) / len(rest)
    total = sum(shares.values()) or 1.0
    return {k: v / total for k, v in shares.items()}


def make_workload(args, vocab_size):
    """The seeded request mix: ``[(prompt, max_new, sampling, tenant), ...]``.
    ``--shared-prefix-len N`` forces one common first-N-token prefix across all
    prompts (truncated for shorter ones) so repeated-prefix reuse is testable.
    With ``--tenants``, each request draws its tenant from the ``share=``
    traffic mix under the same seed — an A-vs-B pair of runs offers
    byte-identical per-tenant workloads."""
    from csed_514_project_distributed_training_using_pytorch_tpu.serving import (
        SamplingParams,
    )

    rng = np.random.default_rng(args.seed)
    lens = prompt_len_mix(args)
    shared = rng.integers(0, vocab_size - 1,
                          size=max(args.shared_prefix_len, 0)).astype(np.int32)
    sampling = SamplingParams(temperature=args.temperature, top_k=args.top_k,
                              top_p=args.top_p)
    shares = tenant_shares(args.tenants) if getattr(args, "tenants", "") \
        else {"default": 1.0}
    names = sorted(shares)
    probs = np.asarray([shares[n] for n in names])
    specs = []
    for _ in range(args.requests):
        p = int(rng.choice(lens))
        prompt = rng.integers(0, vocab_size - 1, size=p).astype(np.int32)
        k = min(len(shared), p)
        if k:
            prompt[:k] = shared[:k]
        new = int(rng.integers(1, args.max_new_tokens + 1))
        tenant = str(rng.choice(names, p=probs))
        specs.append((prompt, new, sampling, tenant))
    return specs


def _tally_refusal(rejections: dict, tenant: str, exc, lock) -> None:
    """The three-way refusal ledger (one owner — open/closed/chat loops all
    report through it): ``QueueFull`` (capacity backpressure),
    ``QuotaExceeded`` (over the tenant's contract), ``Shed`` (priority-
    ordered overload shedding), totals and per tenant."""
    from csed_514_project_distributed_training_using_pytorch_tpu.serving import (
        QueueFull,
        QuotaExceeded,
    )

    key = ("rejected" if isinstance(exc, QueueFull)
           else "quota_rejected" if isinstance(exc, QuotaExceeded)
           else "shed_submits")
    with lock:
        rejections[key] += 1
        rejections["by_tenant"].setdefault(
            tenant, {"rejected": 0, "quota_rejected": 0,
                     "shed_submits": 0})[key] += 1


def _submit_counted(server, spec, futures, rejections, lock):
    """One submit through the refusal ledger; returns the future or None."""
    from csed_514_project_distributed_training_using_pytorch_tpu.serving import (
        QueueFull,
        QuotaExceeded,
        Shed,
    )

    prompt, new, sampling, tenant = spec
    try:
        fut = server.submit(prompt, max_new_tokens=new, sampling=sampling,
                            **({"tenant": tenant}
                               if tenant != "default" else {}))
    except (QueueFull, QuotaExceeded, Shed) as e:
        _tally_refusal(rejections, tenant, e, lock)
        return None
    with lock:
        futures.append(fut)
    return fut


def new_rejections() -> dict:
    return {"rejected": 0, "quota_rejected": 0, "shed_submits": 0,
            "by_tenant": {}}


def run_open_loop(server, specs, rate, rng, *, pattern="poisson",
                  burst_size=8, burst_idle_s=1.0, burst_tenant=""):
    """Open-loop arrivals; returns (futures, rejections dict).

    ``pattern="poisson"`` is the classic memoryless stream at ``rate`` req/s.
    ``pattern="burst"`` is the elasticity workload: ``burst_size`` requests
    arrive back-to-back (an arrival spike that piles the router queue up and
    ages its head — the autoscaler's scale-up signal), then ``burst_idle_s``
    of silence (the valley where utilization falls and a sustained-idle fleet
    earns a scale-down).

    ``burst_tenant`` (with a multi-tenant workload) is the contended-serving
    scenario: THAT tenant's stream arrives in bursts while every other tenant
    stays Poisson at its share of ``rate`` — the committed tenant-burst
    artifact drives exactly this shape (paid steady, best-effort spiking 3x)."""
    futures: list = []
    rejections = new_rejections()
    tenants = sorted({s[3] for s in specs})
    if len(tenants) <= 1 and not burst_tenant:
        lone = threading.Lock()
        for i, spec in enumerate(specs):
            if pattern == "burst":
                if i and i % burst_size == 0:
                    time.sleep(burst_idle_s)
            else:
                time.sleep(float(rng.exponential(1.0 / rate)))
            _submit_counted(server, spec, futures, rejections, lone)
        return futures, rejections
    # Multi-tenant: one arrival stream per tenant (each at its request-count
    # share of the aggregate rate), so tenant mixes are independent processes
    # — a burst on one never thins another's offered load.
    lock = threading.Lock()
    by_tenant = {t: [s for s in specs if s[3] == t] for t in tenants}

    def stream(tenant: str, tspecs, seed: int):
        trng = np.random.default_rng(seed)
        trate = max(rate * len(tspecs) / max(len(specs), 1), 1e-6)
        bursty = (tenant == burst_tenant
                  or (pattern == "burst" and not burst_tenant))
        for i, spec in enumerate(tspecs):
            if bursty:
                if i and i % burst_size == 0:
                    time.sleep(burst_idle_s)
            else:
                time.sleep(float(trng.exponential(1.0 / trate)))
            _submit_counted(server, spec, futures, rejections, lock)

    threads = [threading.Thread(target=stream, args=(t, by_tenant[t], i + 11),
                                name=f"loadgen-{t}")
               for i, t in enumerate(tenants)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    return futures, rejections


def run_closed_loop(server, specs, concurrency):
    """``concurrency`` clients, each one request in flight; returns
    ``(futures, rejections dict)`` — a refused submit sheds the request, the
    client moves on (mirrors the open loop's accounting)."""
    it = iter(specs)
    lock = threading.Lock()
    futures: list = []
    rejections = new_rejections()

    def client():
        while True:
            with lock:
                spec = next(it, None)
            if spec is None:
                return
            fut = _submit_counted(server, spec, futures, rejections, lock)
            if fut is not None:
                fut.result()                    # keep exactly one in flight

    threads = [threading.Thread(target=client, name=f"loadgen-{i}")
               for i in range(concurrency)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    return futures, rejections


def run_chat(front, args, vocab_size):
    """``--sessions`` concurrent multi-turn sessions against ``front`` (Server
    or Router — same ``submit`` surface). Each session thread keeps one request
    in flight: turn t's prompt is the full emitted stream of turn t-1 (context +
    reply) plus ``--turn-user-tokens`` fresh tokens. Greedy decode makes the
    whole workload deterministic given the params, so an A-vs-B pair of runs
    (e.g. affinity on/off) offers byte-identical traffic.

    Returns ``(completions, rejections, sessions_done)`` — a session counts
    done when it ran all its turns (or cleanly hit the seq_len ceiling). With
    ``--tenants``, each SESSION draws its tenant from the ``share=`` mix (a
    session is one user; its turns share a class)."""
    from csed_514_project_distributed_training_using_pytorch_tpu.serving.scheduler import (
        QueueFull,
        QuotaExceeded,
        SamplingParams,
        Shed,
    )

    sampling = SamplingParams(temperature=args.temperature, top_k=args.top_k,
                              top_p=args.top_p)
    lens = [l for l in prompt_len_mix(args) if l > 0] or [1]
    lock = threading.Lock()
    comps: list = []
    rejections = new_rejections()
    done_sessions = [0]
    errors: list = []
    shares = (tenant_shares(args.tenants)
              if getattr(args, "tenants", "") else {"default": 1.0})
    names = sorted(shares)
    probs = np.asarray([shares[n] for n in names])

    def session(sid: int):
        rng = np.random.default_rng(args.seed + 1000 * (sid + 1))
        tenant = str(rng.choice(names, p=probs))
        prompt = rng.integers(0, vocab_size - 1,
                              size=int(rng.choice(lens))).astype(np.int32)
        for _ in range(args.turns):
            new = int(rng.integers(1, args.max_new_tokens + 1))
            if len(prompt) + new >= args.seq_len:
                break                      # context window full: session over
            try:
                fut = front.submit(prompt, max_new_tokens=new,
                                   sampling=sampling,
                                   **({"tenant": tenant}
                                      if tenant != "default" else {}))
            except (QueueFull, QuotaExceeded, Shed) as e:
                _tally_refusal(rejections, tenant, e, lock)
                return                     # overloaded: the session gives up
            comp = fut.result()
            with lock:
                comps.append(comp)
            if not comp.ok:
                return
            user = rng.integers(0, vocab_size - 1,
                                size=args.turn_user_tokens).astype(np.int32)
            prompt = np.concatenate([np.asarray(comp.tokens, np.int32), user])
        with lock:
            done_sessions[0] += 1

    def guarded(sid: int):
        # A failed front end (e.g. ServerStopped after every replica died)
        # must surface as a loadgen failure, not as a silently shorter run.
        try:
            session(sid)
        except BaseException as e:         # noqa: BLE001 — recorded, re-raised
            with lock:
                errors.append((sid, e))

    threads = [threading.Thread(target=guarded, args=(i,), name=f"chat-{i}")
               for i in range(args.sessions)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    if errors:
        sid, first = errors[0]
        raise RuntimeError(
            f"{len(errors)}/{args.sessions} chat sessions died "
            f"(first: session {sid}: {type(first).__name__}: {first})") from first
    return comps, rejections, done_sessions[0]


class _TracedFront:
    """Wrap a ``Server``/``Router`` front end so every loadgen request is a
    trace ORIGIN: a fresh ``trace_id`` per submit (propagated through the
    whole serve path) and a ``client`` span — submit call to future
    resolution, the outermost span of the tree and the latency the user
    actually felt. Everything else (``stop`` etc.) passes through."""

    def __init__(self, inner, tracer):
        self._inner = inner
        self._tracer = tracer

    def submit(self, prompt, **kw):
        from csed_514_project_distributed_training_using_pytorch_tpu.utils.trace import (
            new_trace_id,
        )

        tid = new_trace_id()
        t0 = time.monotonic()
        fut = self._inner.submit(prompt, trace_id=tid, **kw)

        def _done(f, tid=tid, t0=t0):
            try:
                finish = f.result().finish
            except BaseException as e:       # noqa: BLE001 — span records it
                finish = f"error:{type(e).__name__}"
            self._tracer.span("client", tid, t0, time.monotonic(),
                              finish=finish)

        fut.add_done_callback(_done)
        return fut

    def __getattr__(self, name):
        return getattr(self._inner, name)


def build_replica_command(args) -> list[str]:
    """The ``serving/replica.py`` argv mirroring this run's model/engine flags
    (the router appends --port/--replica-id/--heartbeat-dir per replica)."""
    pkg = "csed_514_project_distributed_training_using_pytorch_tpu"
    if getattr(args, "echo", False):
        # Jax-free replicas: the elasticity/router-mechanics smoke — the
        # protocol, lifecycle, and scale paths are the same code, only the
        # engine is a deterministic pure function.
        cmd = ["-m", f"{pkg}.serving.replica", "--echo",
               "--seq-len", str(args.seq_len),
               "--num-levels", str(args.num_levels),
               "--num-slots", str(args.num_slots),
               "--max-pending", str(args.max_pending)]
        if args.echo_delay_s:
            cmd += ["--echo-delay-s", str(args.echo_delay_s)]
        return cmd
    cmd = ["-m", f"{pkg}.serving.replica",
           "--seq-len", str(args.seq_len), "--num-levels", str(args.num_levels),
           "--embed-dim", str(args.embed_dim),
           "--num-layers", str(args.num_layers),
           "--num-heads", str(args.num_heads), "--kv-heads", str(args.kv_heads),
           "--attention-window", str(args.attention_window),
           "--seed", str(args.seed),
           "--num-slots", str(args.num_slots),
           "--max-pending", str(args.max_pending),
           "--timeout-s", str(args.timeout_s),
           "--prefill-chunks", args.prefill_chunks,
           "--prefill-budget", str(args.prefill_budget),
           "--prefix-cache", str(args.prefix_cache),
           "--kv-dtype", args.kv_dtype,
           "--quant-policy", args.quant_policy,
           "--spec", args.spec, "--spec-k", str(args.spec_k),
           "--draft-layers", str(args.draft_layers),
           "--draft-embed-dim", str(args.draft_embed_dim),
           "--draft-heads", str(args.draft_heads),
           "--warmup", str(args.warmup)]
    if getattr(args, "slo", ""):
        cmd += ["--slo", args.slo]
    if args.draft_checkpoint:
        cmd += ["--draft-checkpoint", args.draft_checkpoint]
    if args.rope:
        cmd.append("--rope")
    if args.checkpoint:
        cmd += ["--checkpoint", args.checkpoint]
    if getattr(args, "shard", ""):
        cmd += ["--shard", args.shard]
    return cmd


def main(argv: list[str] | None = None) -> int:
    p = argparse.ArgumentParser(description=__doc__,
                                formatter_class=argparse.RawDescriptionHelpFormatter)
    m = p.add_argument_group("model")
    m.add_argument("--checkpoint", default="",
                   help="TrainState or params msgpack from train.lm (default: "
                        "seeded random init — pure perf mode)")
    m.add_argument("--seq-len", type=int, default=784)
    m.add_argument("--num-levels", type=int, default=16)
    m.add_argument("--embed-dim", type=int, default=64)
    m.add_argument("--num-layers", type=int, default=2)
    m.add_argument("--num-heads", type=int, default=4)
    m.add_argument("--kv-heads", type=int, default=0)
    m.add_argument("--attention-window", type=int, default=0)
    m.add_argument("--rope", action="store_true")
    e = p.add_argument_group("engine/server")
    e.add_argument("--num-slots", type=int, default=8)
    e.add_argument("--max-pending", type=int, default=128)
    e.add_argument("--timeout-s", type=float, default=0.0,
                   help="per-request deadline, 0 = none")
    e.add_argument("--prefill-chunks", default="32,128,512",
                   help="static chunk-size set for batched prefill; empty = "
                        "legacy prefill-as-decode (the A/B switch)")
    e.add_argument("--prefill-budget", type=int, default=1,
                   help="prefill chunk invocations per engine step (decode "
                        "interleaving)")
    e.add_argument("--prefix-cache", type=int, default=0,
                   help="prefix KV cache LRU entries, 0 = off")
    e.add_argument("--prefix-cache-bytes", type=int, default=0,
                   help="measured-byte budget for the prefix cache on top of "
                        "the entry count (0 = entry-count LRU only)")
    e.add_argument("--kv-layout", default="contiguous",
                   choices=("contiguous", "paged"),
                   help="KV store layout: 'paged' decouples slot count from "
                        "max context via a fixed page pool (DESIGN.md §27)")
    e.add_argument("--page-size", type=int, default=64,
                   help="paged layout: tokens per KV page")
    e.add_argument("--num-pages", type=int, default=0,
                   help="paged layout: pool size in pages (0 = capacity "
                        "parity with the contiguous cache)")
    e.add_argument("--kv-dtype", default="model",
                   choices=("model", "fp32", "bf16", "int8", "fp8"),
                   help="KV-cache plane dtype: int8/fp8 = quantize-on-write "
                        "planes with per-head scales (~half/quarter decode "
                        "bytes, ~2-4x slots per HBM budget) — the quant A/B "
                        "switch; 'model' keeps the bitwise-pinned fp32 path")
    e.add_argument("--quant-policy", default="off",
                   choices=("off", "w8", "w8a8"),
                   help="weight-matmul path: w8 = int8 kernels + per-channel "
                        "scales (f32 activations), w8a8 = int8 activations "
                        "too (int8 x int8 -> int32 matmul)")
    e.add_argument("--spec", default="off",
                   choices=("off", "ngram", "draft-lm"),
                   help="speculative decoding (the A/B switch): 'ngram' = "
                        "free host-side n-gram/prompt-lookup self-speculation "
                        "(big wins on --scenario chat), 'draft-lm' = a small "
                        "draft LM sharing the tokenizer")
    e.add_argument("--spec-k", type=int, default=4,
                   help="draft tokens per verify step (verify program width "
                        "= spec_k + 1, one compile)")
    e.add_argument("--draft-layers", type=int, default=1,
                   help="draft LM: transformer layers")
    e.add_argument("--draft-embed-dim", type=int, default=0,
                   help="draft LM: embed dim (0 = half the target's)")
    e.add_argument("--draft-heads", type=int, default=0,
                   help="draft LM: heads (0 = the target's)")
    e.add_argument("--draft-checkpoint", default="",
                   help="trained draft-LM params msgpack (default: seeded "
                        "init)")
    e.add_argument("--slo", default="",
                   help="SLO spec 'ttft=0.5,e2e=2.0,window=30' (obs/slo.py): "
                        "the router (fleet mode) and every replica track "
                        "attainment against it — 'slo' drain events, summary "
                        "dicts, per-replica windows in fleet_snapshot; empty "
                        "= no promise")
    e.add_argument("--tenants", default="",
                   help="tenant service classes + traffic mix, e.g. "
                        "'paid:w=4,prio=2,share=0.25,slo=ttft:0.3;"
                        "free:w=1,preempt=1,share=0.75' — w/prio/rate/burst/"
                        "cap/preempt/slo are the scheduler's service-class "
                        "grammar (quotas, weighted-fair + priority dequeue, "
                        "slot caps, preemption), share= is this loadgen's "
                        "offered-traffic fraction; empty = one anonymous "
                        "tenant (the pre-tenancy behavior)")
    e.add_argument("--warmup", type=int, default=1,
                   help="pre-measurement warmup rounds: compile the decode, "
                        "every prefill chunk size, and the prefix-cache install "
                        "path, then reset the engine's counters — so latency "
                        "percentiles measure the schedule, not XLA (0 = off)")
    e.add_argument("--shard", default="",
                   help="replica-internal serve mesh, e.g. 'tp=2,dp=2' "
                        "(serving/shard.py): every replica shards its params "
                        "over tp chips and its slots over dp groups; on CPU "
                        "the loadgen grows the replicas' host-device count "
                        "via XLA_FLAGS to fit tp*dp virtual chips")
    f = p.add_argument_group("fleet (0 replicas = the in-process server)")
    f.add_argument("--tiers", default="",
                   help="disaggregated prefill/decode tiers, e.g. "
                        "'prefill:1,decode:2' (roles assigned to replicas by "
                        "position, DESIGN.md §25): prefill-tier replicas "
                        "prefill and ship KV planes to decode-tier replicas "
                        "over the framed wire; empty = a unified fleet")
    f.add_argument("--replicas", type=int, default=0,
                   help="run a serving.Router fleet of N replica PROCESSES "
                        "(serving/replica.py) instead of the in-process server")
    f.add_argument("--affinity", choices=("on", "off"), default="on",
                   help="prefix-affinity routing vs least-loaded baseline "
                        "(the router A/B switch)")
    f.add_argument("--echo", action="store_true",
                   help="fleet mode: spawn jax-free --echo replicas "
                        "(deterministic tokens, --echo-delay-s per token) — "
                        "the router-mechanics/elasticity smoke workload")
    f.add_argument("--echo-delay-s", type=float, default=0.0,
                   help="echo replicas: per-token sleep (keeps work in "
                        "flight so load actually accumulates)")
    f.add_argument("--replica-platform", default="",
                   help="JAX_PLATFORMS for replica processes; '' (default) = "
                        "inherit the environment, so replicas land where "
                        "this command would (the accelerator when there is "
                        "one; the CPU tests export JAX_PLATFORMS=cpu)")
    f.add_argument("--router-max-pending", type=int, default=0,
                   help="router admission queue bound (0 = unbounded)")
    f.add_argument("--heartbeat-dir", default="",
                   help="replica liveness dir (default: a temp dir)")
    f.add_argument("--heartbeat-timeout-s", type=float, default=20.0,
                   help="beat staleness that counts a replica as hung")
    f.add_argument("--max-restarts", type=int, default=3,
                   help="per-replica restart budget")
    f.add_argument("--backoff-s", type=float, default=0.5,
                   help="restart backoff base (exponential, capped)")
    s = p.add_argument_group("elasticity (fleet mode)")
    s.add_argument("--autoscale", choices=("on", "off"), default="off",
                   help="drive scale_up/scale_down from the fleet_snapshot "
                        "load signal (hysteresis policy below; needs "
                        "--snapshot-interval-s > 0)")
    s.add_argument("--min-replicas", type=int, default=0,
                   help="scale-down floor (0 = --replicas, i.e. never shrink)")
    s.add_argument("--max-replicas", type=int, default=0,
                   help="scale-up cap (0 = --replicas when autoscaling, "
                        "unbounded for manual scaling)")
    s.add_argument("--scale-up-age-s", type=float, default=0.5,
                   help="queue head older than this counts as overloaded")
    s.add_argument("--scale-up-util", type=float, default=0.95,
                   help="in-flight/capacity at/above this counts as overloaded")
    s.add_argument("--scale-down-util", type=float, default=0.25,
                   help="empty queue + utilization at/below this counts idle")
    s.add_argument("--scale-sustain-up", type=int, default=2,
                   help="consecutive overloaded snapshots before a scale-up")
    s.add_argument("--scale-sustain-down", type=int, default=4,
                   help="consecutive idle snapshots before a scale-down")
    s.add_argument("--scale-cooldown-s", type=float, default=3.0,
                   help="dead time after any scale action")
    s.add_argument("--scale-slo-floor", type=float, default=0.0,
                   help="SLO-attainment objective: windowed attainment below "
                        "this floor counts as overloaded (grow) and BLOCKS "
                        "every shrink — the autoscaler scales on the promise, "
                        "not raw utilization (0 = utilization-only policy)")
    s.add_argument("--scale-slo-tenant", default="",
                   help="watch THIS tenant's windowed attainment from "
                        "fleet_snapshot's tenants section (the high tier) "
                        "instead of the fleet-wide window")
    s.add_argument("--scale-slo-min-requests", type=int, default=5,
                   help="minimum completions in the window before attainment "
                        "is trusted (noise guard)")
    s.add_argument("--warm-prefixes", type=int, default=8,
                   help="hot affinity prefixes a new replica replays before "
                        "it is marked ready (0 = cold starts)")
    s.add_argument("--drain-timeout-s", type=float, default=30.0,
                   help="how long a retiring/reloading replica may finish "
                        "in-flight work before stragglers redispatch")
    gf = p.add_argument_group("gray failures (fleet mode, DESIGN.md §23)")
    gf.add_argument("--straggler-k", type=float, default=0.0,
                    help="straggler ejection: a replica whose windowed "
                         "dispatch p95 exceeds k x the fleet-median peer p95 "
                         "is flipped to 'degraded' (no new dispatch, "
                         "in-flight finishes, probed back after the "
                         "cooldown); 0 = off")
    gf.add_argument("--eject-min-samples", type=int, default=8,
                    help="windowed samples required on the scored replica "
                         "before ejection can trip (noise guard)")
    gf.add_argument("--eject-cooldown-s", type=float, default=5.0,
                    help="degraded dwell before the probe re-opens dispatch")
    gf.add_argument("--hedge", choices=("on", "off"), default="off",
                    help="hedged dispatch: a request still pending past the "
                         "hedge deadline gets a speculative second copy on "
                         "another replica; first completion wins, the loser "
                         "is cancelled over the wire")
    gf.add_argument("--hedge-after-s", type=float, default=0.0,
                    help="fixed hedge deadline in seconds (0 = derive from "
                         "the fleet's windowed dispatch-latency quantile)")
    gf.add_argument("--hedge-quantile", type=float, default=95.0,
                    help="quantile of the windowed fleet dispatch latency "
                         "the derived hedge deadline starts from")
    gf.add_argument("--hedge-factor", type=float, default=2.0,
                    help="multiplier on the quantile for the derived "
                         "deadline")
    gf.add_argument("--chaos", default="",
                    help="network-chaos spec (resilience/netfaults.py "
                         "grammar, e.g. 'delay:replica=1,ms=800,count=20;"
                         "corrupt:replica=0,after=5'): route every "
                         "router<->replica connection through a seeded "
                         "in-process fault-injecting proxy")
    gf.add_argument("--chaos-seed", type=int, default=0,
                    help="seed for the chaos proxy's corrupt-byte positions")
    gf.add_argument("--framed-wire", choices=("on", "off"), default="on",
                    help="negotiate length+CRC wire framing with replicas "
                         "that advertise it ('off' pins the legacy newline "
                         "protocol — the back-compat A/B switch)")
    g = p.add_argument_group("load")
    g.add_argument("--scenario", choices=("batch", "chat"), default="batch",
                   help="'batch' = independent requests (open/closed loop); "
                        "'chat' = multi-turn sessions, each turn resubmitting "
                        "prior context + reply (the prefix-affinity workload)")
    g.add_argument("--sessions", type=int, default=8,
                   help="chat: concurrent sessions")
    g.add_argument("--turns", type=int, default=4,
                   help="chat: turns per session")
    g.add_argument("--turn-user-tokens", type=int, default=4,
                   help="chat: fresh 'user' tokens appended between turns")
    g.add_argument("--mode", choices=("open", "closed"), default="open")
    g.add_argument("--rate", type=float, default=8.0,
                   help="open loop: Poisson arrival rate, req/s")
    g.add_argument("--arrival-pattern", choices=("poisson", "burst"),
                   default="poisson",
                   help="open loop: 'burst' sends --burst-size requests "
                        "back-to-back then idles --burst-idle-s (the "
                        "autoscaler exercise: spike -> grow, valley -> shrink)")
    g.add_argument("--burst-size", type=int, default=8,
                   help="burst pattern: requests per spike")
    g.add_argument("--burst-idle-s", type=float, default=1.0,
                   help="burst pattern: idle valley between spikes")
    g.add_argument("--burst-tenant", default="",
                   help="with --tenants: only THIS tenant's arrival stream "
                        "bursts (back-to-back spikes) while the others stay "
                        "Poisson — the contended two-tenant scenario the "
                        "tenant-burst artifact drives (best-effort spikes, "
                        "paid holds its SLO)")
    g.add_argument("--concurrency", type=int, default=4,
                   help="closed loop: clients with one request in flight each")
    g.add_argument("--requests", type=int, default=32)
    g.add_argument("--prompt-dist", choices=("custom", "long"), default="custom",
                   help="'long' = prompt-heavy mixture (seq_len/2..3/4) that "
                        "exercises prefill; 'custom' uses --prompt-lens")
    g.add_argument("--prompt-lens", default="0,16,64",
                   help="comma list; each request draws uniformly from it")
    g.add_argument("--shared-prefix-len", type=int, default=0,
                   help="force a common first-N-token prefix across prompts "
                        "(exercises the prefix KV cache)")
    g.add_argument("--max-new-tokens", type=int, default=32,
                   help="each request draws its length from [1, this]")
    g.add_argument("--temperature", type=float, default=0.0)
    g.add_argument("--top-k", type=int, default=0)
    g.add_argument("--top-p", type=float, default=1.0)
    g.add_argument("--seed", type=int, default=0)
    p.add_argument("--telemetry", default="",
                   help="serve JSONL path (render with tools/telemetry_report.py)")
    p.add_argument("--trace-dir", default="",
                   help="distributed-tracing span dir: this loadgen writes "
                        "loadgen.jsonl (client spans + per-request trace_id "
                        "origin), the router/server and every replica write "
                        "their own span files under it — render with "
                        "tools/trace_report.py")
    p.add_argument("--snapshot-interval-s", type=float, default=0.0,
                   help="fleet mode: the router emits a fleet_snapshot "
                        "metrics-timeline event every N seconds (the "
                        "elasticity load signal; needs --telemetry, 0 = off)")
    p.add_argument("--summary-json", default="",
                   help="write the run summary (percentiles + prefill stats) "
                        "as one JSON document — the committed-artifact format")
    args = p.parse_args(argv)
    if args.scenario == "batch":
        if args.mode == "open" and args.rate <= 0:
            raise SystemExit("--rate must be > 0 in open-loop mode")
        if args.mode == "closed" and args.concurrency < 1:
            raise SystemExit("--concurrency must be >= 1 in closed-loop mode")
    elif args.sessions < 1 or args.turns < 1:
        raise SystemExit("--sessions and --turns must be >= 1 in chat mode")
    if args.max_new_tokens < 1:
        raise SystemExit("--max-new-tokens must be >= 1")
    if args.echo and args.replicas < 1:
        raise SystemExit("--echo needs --replicas N (echo replicas are a "
                         "fleet-mode workload)")
    tier_roles: list[str] = []
    if args.tiers:
        from csed_514_project_distributed_training_using_pytorch_tpu.serving.tiers import (
            parse_tier_spec,
        )

        if args.replicas < 1:
            raise SystemExit("--tiers needs --replicas N (tiered serving is "
                             "a fleet-mode workload)")
        try:
            tier_roles = parse_tier_spec(args.tiers)
        except ValueError as exc:
            raise SystemExit(str(exc))
        if len(tier_roles) != args.replicas:
            raise SystemExit(
                f"--tiers names {len(tier_roles)} replica role(s) but "
                f"--replicas is {args.replicas} — the spec assigns roles by "
                f"position and must cover the whole fleet")
    shard_tp = shard_dp = 1
    if args.shard:
        from csed_514_project_distributed_training_using_pytorch_tpu.serving.tiers import (
            parse_shard_spec,
        )

        if args.echo:
            raise SystemExit("--shard needs a real engine (echo replicas "
                             "build no mesh)")
        try:
            shard_tp, shard_dp = parse_shard_spec(args.shard)
        except ValueError as exc:
            raise SystemExit(str(exc))
    if args.burst_tenant:
        known = set(tenant_shares(args.tenants)) if args.tenants else set()
        if args.burst_tenant not in known:
            # A typo here would silently disable ALL bursting and report an
            # unloaded run as the loaded leg of an A/B — fail loudly instead.
            raise SystemExit(
                f"--burst-tenant {args.burst_tenant!r} is not one of the "
                f"--tenants names {sorted(known) or '(none declared)'}")

    vocab_size = args.num_levels + 1
    tracer = None
    if args.trace_dir:
        # This loadgen is the trace ORIGIN: it writes loadgen.jsonl (the
        # outermost "client" spans) and every downstream process writes its own
        # span file under the same dir — see utils/trace.py.
        from csed_514_project_distributed_training_using_pytorch_tpu.utils.trace import (
            Tracer,
        )

        tracer = Tracer(os.path.join(args.trace_dir, "loadgen.jsonl"),
                        proc="loadgen")
    engine = server = router = None
    if args.replicas > 0:
        # Fleet mode: the model lives in the replica processes; this process
        # stays backend-free (the router supervises accelerator owners).
        import tempfile

        from csed_514_project_distributed_training_using_pytorch_tpu.obs.slo import (
            SLOSpec,
        )
        from csed_514_project_distributed_training_using_pytorch_tpu.serving.router import (
            Router,
        )
        from csed_514_project_distributed_training_using_pytorch_tpu.serving.scheduler import (
            parse_tenants,
        )

        # Replica processes must import this package no matter the caller's
        # cwd — ship the repo root (already first on OUR sys.path, line 53)
        # through their PYTHONPATH.
        repo_root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        env = dict(os.environ)
        env["PYTHONPATH"] = (f"{repo_root}:{env['PYTHONPATH']}"
                             if env.get("PYTHONPATH") else repo_root)
        if shard_tp * shard_dp > 1 and (
                args.replica_platform or env.get("JAX_PLATFORMS")) == "cpu":
            # A CPU replica has one host device by default; grow it so the
            # tp*dp serve mesh has chips to land on (the same trick the test
            # suite uses — a multi-process CPU "mesh" of virtual devices).
            flag = (f"--xla_force_host_platform_device_count="
                    f"{shard_tp * shard_dp}")
            env["XLA_FLAGS"] = (f"{env['XLA_FLAGS']} {flag}"
                                if env.get("XLA_FLAGS") else flag)
        autoscale = None
        if args.autoscale == "on":
            from csed_514_project_distributed_training_using_pytorch_tpu.serving.autoscaler import (
                AutoscalePolicy,
            )

            autoscale = AutoscalePolicy(
                min_replicas=args.min_replicas or args.replicas,
                max_replicas=args.max_replicas or args.replicas,
                up_queue_age_s=args.scale_up_age_s,
                up_utilization=args.scale_up_util,
                down_utilization=args.scale_down_util,
                sustain_up=args.scale_sustain_up,
                sustain_down=args.scale_sustain_down,
                cooldown_s=args.scale_cooldown_s,
                slo_floor=args.scale_slo_floor or None,
                slo_tenant=args.scale_slo_tenant or None,
                slo_min_requests=args.scale_slo_min_requests)
        router = Router(
            build_replica_command(args), num_replicas=args.replicas,
            platform=args.replica_platform or None,
            max_pending=args.router_max_pending,
            default_timeout_s=args.timeout_s or None,
            affinity=args.affinity == "on",
            heartbeat_dir=args.heartbeat_dir or tempfile.mkdtemp(
                prefix="serve_hb_"),
            heartbeat_timeout_s=args.heartbeat_timeout_s,
            max_restarts=args.max_restarts, backoff_s=args.backoff_s,
            telemetry=args.telemetry, trace_dir=args.trace_dir,
            snapshot_interval_s=args.snapshot_interval_s,
            autoscale=autoscale,
            min_replicas=args.min_replicas or None,
            max_replicas=args.max_replicas or None,
            warm_prefixes=args.warm_prefixes,
            drain_timeout_s=args.drain_timeout_s,
            straggler_k=args.straggler_k,
            eject_min_samples=args.eject_min_samples,
            eject_cooldown_s=args.eject_cooldown_s,
            hedge=args.hedge == "on",
            hedge_after_s=args.hedge_after_s,
            hedge_quantile=args.hedge_quantile,
            hedge_factor=args.hedge_factor,
            chaos=args.chaos, chaos_seed=args.chaos_seed,
            framed_wire=args.framed_wire == "on",
            slo=SLOSpec.parse(args.slo),
            # The router is the fleet's ONE quota-charging front door; the
            # replica argv deliberately omits --tenants (per-request tenancy
            # fields ride the wire instead) so admission is never charged
            # twice.
            tenants=parse_tenants(args.tenants), env=env,
            replica_extra_args=([["--tier", role] for role in tier_roles]
                                if tier_roles else None))
        front = router.start()
        if not router.wait_ready(timeout=600):
            router.stop(drain=False)
            raise SystemExit("fleet did not come up within 600s "
                             "(or crash-looped its restart budget away — "
                             "check the replica command/stderr)")
    else:
        # The in-process baseline is built by the SAME code path as a fleet
        # replica (model construction, checkpoint-format fallback, warmup
        # recipe) — one owner, so the single-engine and fleet sides of an A/B
        # can never drift apart.
        if (shard_tp * shard_dp > 1
                and os.environ.get("JAX_PLATFORMS") == "cpu"):
            # Same trick as the fleet path, applied to OUR process: grow the
            # single host CPU device into tp*dp virtual chips. XLA reads the
            # flag at backend INITIALIZATION (first devices() call, inside
            # the engine build below), so setting it here is early enough
            # even though the package import already loaded the jax module.
            flag = (f"--xla_force_host_platform_device_count="
                    f"{shard_tp * shard_dp}")
            os.environ["XLA_FLAGS"] = \
                (os.environ.get("XLA_FLAGS", "") + " " + flag).strip()
        from csed_514_project_distributed_training_using_pytorch_tpu.serving.replica import (
            build_engine_server,
        )

        engine, server = build_engine_server(
            args, trace=(os.path.join(args.trace_dir, "server.jsonl")
                         if args.trace_dir else None))
        front = server.start()
    if tracer is not None:
        front = _TracedFront(front, tracer)

    t0 = time.monotonic()
    sessions_done = None
    try:
        if args.scenario == "chat":
            comps, rejections, sessions_done = run_chat(front, args, vocab_size)
        else:
            specs = make_workload(args, vocab_size)
            if args.mode == "open":
                futures, rejections = run_open_loop(
                    front, specs, args.rate, np.random.default_rng(args.seed + 1),
                    pattern=args.arrival_pattern,
                    burst_size=args.burst_size,
                    burst_idle_s=args.burst_idle_s,
                    burst_tenant=args.burst_tenant)
            else:
                futures, rejections = run_closed_loop(front, specs,
                                                      args.concurrency)
            comps = [f.result() for f in futures]
        rejected = rejections["rejected"]
    except BaseException:
        # Never orphan replica processes on a failed run.
        try:
            front.stop(drain=False)
        except Exception:
            pass
        raise
    # Wall stops when the last completion is in hand: stop() below pays stats
    # collection + replica teardown, which served no tokens and must not
    # deflate the committed tokens_per_s.
    wall = time.monotonic() - t0
    router_summary = None
    if router is not None:
        router_summary = router.stop(timeout=600)   # graceful drain + stats
    else:
        server.stop()                               # graceful drain (a no-op by now)
    if tracer is not None:
        tracer.close()     # after stop(): every client span's callback has run

    ok = sum(c.ok for c in comps)
    timeouts = sum(c.finish == "timeout" for c in comps)
    shed_comps = sum(c.finish == "shed" for c in comps)
    new_tokens = sum(c.new_tokens for c in comps)
    label = (f"chat ({args.sessions} sessions x {args.turns} turns)"
             if args.scenario == "chat" else f"{args.mode}-loop")
    print(f"{label}: {len(comps)} completed ({ok} ok, {timeouts} timeout, "
          f"{shed_comps} shed, {rejected} rejected, "
          f"{rejections['quota_rejected']} over-quota, "
          f"{rejections['shed_submits']} shed-at-submit) in {wall:.2f}s"
          + (f", {sessions_done}/{args.sessions} sessions ran to completion"
             if sessions_done is not None else ""))

    def comp_tenant(c) -> str:
        t = getattr(c, "tenant", None)
        if t is None:
            t = getattr(getattr(c, "request", None), "tenant", None)
        return t or "default"

    tenant_rows = None
    if args.tenants:
        from csed_514_project_distributed_training_using_pytorch_tpu.utils.jsonl import (
            percentiles as _pcts,
        )

        tenant_rows = {}
        for t in sorted({comp_tenant(c) for c in comps}
                        | set(rejections["by_tenant"])):
            tc = [c for c in comps if comp_tenant(c) == t]
            rej = rejections["by_tenant"].get(t) or {}
            tenant_rows[t] = {
                "requests": len(tc),
                "ok": sum(c.ok for c in tc),
                "timeout": sum(c.finish == "timeout" for c in tc),
                "shed": sum(c.finish == "shed" for c in tc),
                "preemptions": sum(getattr(c, "preemptions", 0) for c in tc),
                "new_tokens": sum(c.new_tokens for c in tc),
                "ttft_s": _pcts([c.ttft_s for c in tc]),
                "e2e_s": _pcts([c.e2e_s for c in tc]),
                **rej,
            }
            row = tenant_rows[t]
            p95 = (row["ttft_s"] or {}).get("p95")
            print(f"tenant {t}: {row['requests']} requests "
                  f"({row['ok']} ok, {row['timeout']} timeout, "
                  f"{row['shed']} shed, {row['preemptions']} preemption(s)), "
                  f"ttft p95 {'-' if p95 is None else f'{p95:.3f}'}s")
    if router is not None:
        rs = router_summary
        pc = rs.get("prefix_cache") or {}
        hit_rate = (pc["hits"] / pc["queries"] if pc.get("queries") else None)
        aff = rs["affinity_rate"]
        print(f"fleet: {args.replicas} replicas, affinity {args.affinity}: "
              f"{new_tokens} tokens, {new_tokens / wall:.1f} tokens/s, "
              f"affinity rate {'-' if aff is None else f'{aff:.2f}'}, "
              f"prefix hit rate {'-' if hit_rate is None else f'{hit_rate:.2f}'}")
        print(f"resilience: {rs['redispatches']} redispatches "
              f"({rs['redispatched_requests']} requests), "
              f"{rs['replica_restarts']} replica restart(s), "
              f"{rs['duplicates']} duplicate completion(s)")
        if (rs.get("ejections") or rs.get("hedges")
                or rs.get("wire_corrupt")):
            win = rs.get("hedge_win_rate")
            print(f"gray failures: {rs.get('ejections', 0)} ejection(s), "
                  f"{rs.get('probes', 0)} probe recover(ies), "
                  f"{rs.get('hedges', 0)} hedge(s) "
                  f"(win rate {'-' if win is None else f'{win:.2f}'}), "
                  f"{rs.get('wire_corrupt', 0)} typed wire fault(s)")
        if rs.get("handoffs") or rs.get("handoff_failures"):
            disagg = sum(getattr(c, "disagg", False) for c in comps)
            print(f"tiers ({args.tiers or '?'}): {rs.get('handoffs', 0)} "
                  f"kv handoff(s), {rs.get('handoff_bytes', 0)} bytes shipped, "
                  f"{rs.get('handoff_failures', 0)} bounced to local prefill, "
                  f"{disagg} request(s) served disaggregated")
        sp = rs.get("spec") or {}
        if sp:
            rate = sp.get("acceptance_rate")
            tps = sp.get("accepted_tokens_per_step")
            print(f"spec: {sp.get('mode')} k={sp.get('k')}: "
                  f"{sp['accepted']}/{sp['proposed']} drafts accepted "
                  f"(rate {'-' if rate is None else f'{rate:.2f}'}), "
                  f"{'-' if tps is None else f'{tps:.2f}'} accepted tok/step "
                  f"fleet-wide")
        fleet_slo = rs.get("slo")
        if fleet_slo:
            att = fleet_slo.get("attainment")
            print(f"slo: attainment "
                  f"{'-' if att is None else f'{att:.3f}'} "
                  f"({fleet_slo.get('met')}/{fleet_slo.get('requests')} met "
                  f"vs {args.slo})")
        if rs.get("preemptions") or rs.get("resumes"):
            print(f"preemption: {rs.get('preemptions')} park(s), "
                  f"{rs.get('resumes')} resume(s) fleet-wide")
        sc = rs.get("scale") or {}
        if rs.get("scale_events"):
            print(f"elasticity: {sc.get('scale_ups', 0)} scale-up(s), "
                  f"{sc.get('retired', 0)} graceful retire(s), "
                  f"{sc.get('reloads', 0)} reload(s); "
                  f"replicas ready p50 "
                  f"{rs.get('replicas_ready_p50') or '-'} / max "
                  f"{rs.get('replicas_ready_max') or '-'} "
                  f"(target ended at {rs.get('target')})")
    else:
        occ = engine.slot_occupancy             # None when no step ever ran
        print(f"generated {new_tokens} tokens, {new_tokens / wall:.1f} tokens/s, "
              f"slot occupancy {'-' if occ is None else f'{occ:.2f}'}, "
              f"decode compilations {engine.trace_count}")
        prefill_rate = (engine.prefill_tokens / engine.prefill_wall_s
                        if engine.prefill_wall_s else None)
        sp = engine.spec_stats()
        if sp:
            rate = sp.get("acceptance_rate")
            tps = sp.get("accepted_tokens_per_step")
            print(f"spec: {sp['mode']} k={sp['k']}: "
                  f"{sp['accepted']}/{sp['proposed']} drafts accepted "
                  f"(rate {'-' if rate is None else f'{rate:.2f}'}), "
                  f"{'-' if tps is None else f'{tps:.2f}'} accepted tok/step, "
                  f"{engine.generated_tokens} tokens in {engine.steps} "
                  f"program invocations")
        srv_slo = server.slo_summary()
        if srv_slo:
            att = srv_slo.get("attainment")
            print(f"slo: attainment "
                  f"{'-' if att is None else f'{att:.3f}'} "
                  f"({srv_slo.get('met')}/{srv_slo.get('requests')} met "
                  f"vs {args.slo})")
        if engine.preemptions or engine.resumes:
            print(f"preemption: {engine.preemptions} park(s), "
                  f"{engine.resumes} resume(s)")
        hits = engine.prefix_cache.stats() if engine.prefix_cache else None
        print(f"prefilled {engine.prefill_tokens} prompt tokens in "
              f"{engine.prefill_invocations} chunks "
              f"({'-' if prefill_rate is None else f'{prefill_rate:.1f}'} tokens/s, "
              f"sizes {list(engine.prefill_chunk_sizes) or 'off'})"
              + (f", prefix hits {hits['hits']}/{hits['queries']} "
                 f"({hits['hit_tokens']} tokens reused)" if hits else ""))
        acct = engine.byte_accounting()
        print(f"bytes (measured): kv {acct['kv_dtype']} / weights "
              f"{acct['quant_policy']}, {acct['kv_bytes_per_slot']} B/slot, "
              f"{acct['decode_bytes_per_token']:.0f} B decode read/token, "
              f"{acct['slots_at_budget']} slots per "
              f"{acct['hbm_budget_bytes'] >> 30} GiB budget")
    if args.telemetry:
        print(f"serve telemetry -> {args.telemetry} "
              f"(render: python tools/telemetry_report.py {args.telemetry})")
    trace_summary = None
    if args.trace_dir:
        # Reduce the span files the run just wrote (loadgen + router/server +
        # every replica) to the critical-path summary; the full per-request
        # trees render via tools/trace_report.py.
        from csed_514_project_distributed_training_using_pytorch_tpu.utils.trace import (
            read_spans,
            summarize_traces,
        )

        spans, _ = read_spans([args.trace_dir])
        trace_summary = summarize_traces(spans)
        seg = trace_summary["segments"]
        top = sorted(seg, key=lambda n: -(seg[n]["p50"] or 0))[:3]
        path = ", ".join(f"{n} p50 {(seg[n]['p50'] or 0) * 1e3:.1f}ms"
                         for n in top)
        print(f"trace: {trace_summary['traces']} traces, "
              f"{trace_summary['spans']} spans, "
              f"{trace_summary['orphans']} orphans, "
              f"{trace_summary['redispatched']} redispatched"
              + (f"; critical path {path}" if path else ""))
        print(f"trace spans -> {args.trace_dir} "
              f"(render: python tools/trace_report.py {args.trace_dir}"
              + (f" {args.telemetry}" if args.telemetry else "") + ")")
    if args.summary_json:
        import json

        from csed_514_project_distributed_training_using_pytorch_tpu.utils.jsonl import (
            percentiles,
        )

        doc = {
            "scenario": args.scenario,
            "mode": args.mode if args.scenario == "batch" else None,
            "requests": len(comps), "ok": ok, "timeout": timeouts,
            "shed": shed_comps, "rejected": rejected,
            "quota_rejected": rejections["quota_rejected"],
            "shed_submits": rejections["shed_submits"],
            "tenants_spec": args.tenants or None,
            "burst_tenant": args.burst_tenant or None,
            "tenants": tenant_rows,
            "wall_s": wall,
            "prompt_dist": args.prompt_dist,
            "prompt_lens": prompt_len_mix(args),
            "shared_prefix_len": args.shared_prefix_len,
            "num_slots": args.num_slots,
            "prefill_chunk_budget": args.prefill_budget,
            "prefix_cache_entries": args.prefix_cache,
            "kv_dtype": args.kv_dtype,
            "quant_policy": args.quant_policy,
            "spec": args.spec,
            "spec_k": args.spec_k if args.spec != "off" else None,
            "new_tokens": new_tokens,
            "tokens_per_s": new_tokens / wall if wall else None,
            "ttft_s": percentiles([c.ttft_s for c in comps]),
            "e2e_s": percentiles([c.e2e_s for c in comps]),
            "queue_wait_s": percentiles([c.queue_wait_s for c in comps]),
            "slo": args.slo or None,
        }
        if args.scenario == "chat":
            doc.update(sessions=args.sessions, turns=args.turns,
                       turn_user_tokens=args.turn_user_tokens,
                       sessions_done=sessions_done)
        if router is not None:
            rs = router_summary
            pc = rs.get("prefix_cache") or {}
            doc.update(
                replicas=args.replicas, affinity=args.affinity,
                echo=args.echo, autoscale=args.autoscale,
                arrival_pattern=(args.arrival_pattern
                                 if args.scenario == "batch"
                                 and args.mode == "open" else None),
                scale=rs.get("scale"),
                scale_events=rs.get("scale_events"),
                target=rs.get("target"),
                replicas_ready_p50=rs.get("replicas_ready_p50"),
                replicas_ready_max=rs.get("replicas_ready_max"),
                replicas_ready_min=rs.get("replicas_ready_min"),
                affinity_rate=rs["affinity_rate"],
                redispatches=rs["redispatches"],
                redispatched_requests=rs["redispatched_requests"],
                duplicate_completions=rs["duplicates"],
                hedge=args.hedge, straggler_k=args.straggler_k or None,
                chaos=args.chaos or None,
                ejections=rs.get("ejections"),
                probes=rs.get("probes"),
                hedges=rs.get("hedges"),
                hedge_wins=rs.get("hedge_wins"),
                hedge_win_rate=rs.get("hedge_win_rate"),
                wire_corrupt=rs.get("wire_corrupt"),
                replica_restarts=rs["replica_restarts"],
                prefix_cache=rs.get("prefix_cache"),
                prefix_hit_rate=(pc["hits"] / pc["queries"]
                                 if pc.get("queries") else None),
                spec_stats=rs.get("spec"),
                tiers=args.tiers or None,
                shard=args.shard or None,
                handoffs=rs.get("handoffs"),
                handoff_bytes=rs.get("handoff_bytes"),
                handoff_failures=rs.get("handoff_failures"),
                disagg_requests=sum(getattr(c, "disagg", False)
                                    for c in comps),
                per_replica=[{k: r[k] for k in ("replica", "state", "restarts",
                                                "dispatched", "completed",
                                                "tier", "handoffs")
                              if k in r}
                             for r in rs["per_replica"]],
                slo_attainment=rs.get("slo"),
                replica_latency=rs.get("replica_latency"),
                tenant_summary=rs.get("tenants"),
                preemptions=rs.get("preemptions"),
                resumes=rs.get("resumes"),
                router_queue=rs.get("queue"))
        else:
            doc.update(
                shard=args.shard or None,
                bytes=engine.byte_accounting(),
                prefill_chunk_sizes=list(engine.prefill_chunk_sizes),
                prefill_tokens=engine.prefill_tokens,
                prefill_chunks=engine.prefill_invocations,
                prefill_wall_s=engine.prefill_wall_s,
                prefill_tokens_per_s=prefill_rate,
                prefix_cache=hits,
                prefix_hit_rate=(hits["hits"] / hits["queries"]
                                 if hits and hits["queries"] else None),
                decode_compilations=engine.trace_count,
                prefill_compilations=dict(engine.prefill_trace_counts),
                decode_invocations=engine.steps,
                generated_tokens=engine.generated_tokens,
                spec_stats=engine.spec_stats(),
                slo_attainment=server.slo_summary(),
                tenant_summary=server.tenant_summaries() or None,
                preemptions=engine.preemptions,
                resumes=engine.resumes,
                verify_compilations=dict(engine.verify_trace_counts))
        if trace_summary is not None:
            # The run carries its trace with it: where the spans live plus the
            # span-derived critical-path percentiles, next to the serve
            # percentiles above — an A/B pair of summaries is self-contained.
            from csed_514_project_distributed_training_using_pytorch_tpu.utils.trace import (
                reconcile_ttft,
            )

            events = []
            if args.telemetry and os.path.exists(args.telemetry):
                from csed_514_project_distributed_training_using_pytorch_tpu.utils.jsonl import (
                    read_jsonl,
                )

                events = read_jsonl(args.telemetry)
            doc["trace"] = {
                "dir": args.trace_dir,
                "traces": trace_summary["traces"],
                "spans": trace_summary["spans"],
                "orphans": trace_summary["orphans"],
                "redispatched": trace_summary["redispatched"],
                "segments": trace_summary["segments"],
                "ttft_s": trace_summary["ttft_s"],
                "e2e_s": trace_summary["e2e_s"],
                "ttft_reconciliation": reconcile_ttft(trace_summary, events),
            }
        with open(args.summary_json, "w") as f:
            json.dump(doc, f, indent=1)
        print(f"summary json -> {args.summary_json}")
    return 0


def _cli() -> int:
    """Script entry: with ``--replicas 0`` THIS process compiles the engine, so
    the persistent compile cache goes on first (fleet replicas enable their
    own in ``serving/replica.py``; enabling imports jax but initializes no
    backend). Kept out of :func:`main` so in-process callers keep jax's
    default config."""
    from csed_514_project_distributed_training_using_pytorch_tpu.utils.compile_cache import (
        enable_compile_cache,
    )

    enable_compile_cache()
    return main()


if __name__ == "__main__":
    sys.exit(_cli())
