"""Configuration system.

The reference configures runs through hardcoded module constants (reference
``src/train.py:12-21``, ``src/train_dist.py:124-139``), one CLI flag (``--local_rank``,
``src/train_dist.py:121``), and cluster env vars set inside the program
(``MASTER_ADDR``/``MASTER_PORT``, ``src/train_dist.py:144-145``). Here the same knob set lives
in two frozen dataclasses with CLI overrides; cluster topology is *not* a knob — it comes from
the runtime (``jax.distributed`` slice metadata / device mesh), which deletes the reference's
hand-edited ``run1.py``/``run2.py`` launcher pattern entirely.

Defaults reproduce the reference values exactly (cited per field).
"""

from __future__ import annotations

import argparse
import dataclasses
from dataclasses import dataclass


@dataclass(frozen=True)
class SingleProcessConfig:
    """Knobs of the single-process trainer (reference ``src/train.py:12-21``)."""

    n_epochs: int = 3                 # src/train.py:12
    batch_size_train: int = 64        # src/train.py:13
    batch_size_test: int = 1000       # src/train.py:14
    learning_rate: float = 0.01       # src/train.py:15
    momentum: float = 0.5             # src/train.py:16
    optimizer: str = "sgd"            # 'sgd' (reference parity, src/train.py:60-61) or
                                      # 'adamw' (beyond-parity; torch.optim.AdamW
                                      # semantics, ops/optim.py — momentum is then unused)
    weight_decay: float = 0.0         # AdamW decoupled weight decay (adamw only)
    lr_schedule: str = "constant"     # learning-rate schedule: 'constant' or 'cosine'
                                      # (half-period decay over the whole run); applied
                                      # inside the compiled step from state.step. This
                                      # trainer's resume trains n_epochs MORE, so the
                                      # cosine horizon anchors at the restored step
                                      # (the resumed run decays over its own span)
    warmup_steps: int = 0             # linear warmup ramp over the first N updates
    clip_grad_norm: float = 0.0       # clip gradients to this global norm before the
                                      # update (torch clip_grad_norm_ semantics); 0 off
    label_smoothing: float = 0.0      # torch CrossEntropyLoss(label_smoothing=s)
                                      # semantics: smoothed target (1-s)*onehot + s/C
    ema_decay: float = 0.0            # maintain an EMA of the params in the compiled
                                      # step (torch swa_utils semantics); eval and the
                                      # final export use the EMA weights; 0 disables
    async_checkpoint: bool = False    # write checkpoints on a background thread
                                      # (serialization+IO off the hot loop; atomic,
                                      # coalescing overwrites; flushed at exit)
    log_interval: int = 10            # src/train.py:17
    seed: int = 1                     # src/train.py:19 (torch.manual_seed(random_seed))
    data_dir: str = "files"           # src/train.py:26 ({CURR_PATH}/files/; one dir, not the
                                      # reference's hardcoded /home/abhishek test path, §2d.2)
    download_data: bool = False       # fetch the MNIST IDX archives into data_dir first
                                      # (≙ torchvision download=True, src/train.py:26-31;
                                      # off by default — this build env has no egress)
    results_dir: str = "results"      # src/train.py:84-85 checkpoint target
    images_dir: str = "images"        # src/train.py:57,117 plot target
    profile: bool = False             # optional jax.profiler capture (reference has none, §5)
    profile_dir: str = "results/profile"
    telemetry: str = ""               # write structured run telemetry (manifest /
                                      # compile / epoch / health / mfu JSONL events,
                                      # utils/telemetry.py) to this path; "" off.
                                      # Render with tools/telemetry_report.py
    health_stats: bool = False        # accumulate grad-norm/param-norm/loss-range
                                      # health stats INSIDE the compiled epoch scan
                                      # (zero extra host syncs; bitwise-identical
                                      # training — train/step.py::HealthStats) and
                                      # emit them as telemetry 'health' events
    resume_from: str = ""             # checkpoint path to resume from (the restore path the
                                      # reference lacks, SURVEY.md §5 "checkpoint/resume")
    model: str = "cnn"                # model family: 'cnn' (the reference's Net) or
                                      # 'transformer' (the beyond-parity attention family,
                                      # models/transformer.py); same data/trainer surface
    bf16: bool = False                # bfloat16 activations (f32 master weights + f32
                                      # softmax/loss statistics — the MXU-native dtype)
    remat: bool = False               # jax.checkpoint each transformer block on backward
                                      # (O(1)-blocks activation memory; transformer only)
    remat_policy: str = ""            # what remat saves: 'recompute-all' (default) or
                                      # 'save-dots' (keep MXU outputs, replay VPU work)
    causal: bool = False              # decoder-style (causal) attention
                                      # (transformer only)
    attention_window: int = 0         # sliding-window (local) attention width
                                      # (transformer only; 0 = full attention; see
                                      # ops.full_attention's window semantics)
    kv_heads: int = 0                 # grouped-query attention: number of K/V heads
                                      # (transformer only; 0 = MHA; must divide
                                      # num_heads — 1 = multi-query attention)
    rope: bool = False                # rotary position embeddings on q/k
                                      # (transformer only; composes with every core)
    use_pallas_kernels: bool = False  # fused Pallas loss/optimizer kernels
                                      # (ops/pallas_kernels.py; single-device step path)
    heartbeat_dir: str = ""           # write a per-process liveness file (step +
                                      # timestamp, atomic) each epoch for the fleet
                                      # supervisor's hang detection
                                      # (resilience/heartbeat.py); "" off
    handle_preemption: bool = False   # SIGTERM/SIGINT request a cooperative stop at
                                      # the next epoch boundary: final checkpoint +
                                      # telemetry flush, then exit 75 ("preempted",
                                      # resumable — resilience/preemption.py)
    keep_checkpoints: int = 0         # ALSO keep the last N per-epoch checkpoints
                                      # under results_dir/checkpoints/ with a
                                      # checksummed manifest + GC — the versioned
                                      # store the supervisor's newest-HEALTHY
                                      # resume scan reads (utils/checkpoint.py);
                                      # 0 off
    guard: bool = False               # numerical immune system: a fixed-shape
                                      # anomaly verdict (non-finite loss/grads,
                                      # grad-norm z-score) computed INSIDE the
                                      # compiled step; a poisoned step applies
                                      # the IDENTITY update instead of garbage
                                      # (train/step.py::GuardSpec). Off = zero
                                      # added ops, bitwise-pinned
    guard_zscore: float = 8.0         # spike threshold: grad norm above
                                      # ema_mean + z*max(ema_std, 0.5*ema_mean)
                                      # is an anomaly (guard only)
    anomaly_exit: int = 0             # exit 65 ("poisoned", EX_DATAERR) at the
                                      # epoch boundary once >= N anomalies were
                                      # detected — the supervisor then rolls
                                      # back to the newest HEALTHY checkpoint
                                      # and restarts with --skip-steps; 0 =
                                      # never exit, keep skipping silently
    skip_steps: str = ""              # half-open step windows "a:b[,c:d]" that
                                      # take the identity update on replay (the
                                      # supervisor's rollback-and-skip handoff;
                                      # deterministic because data order is a
                                      # pure function of seed+step)
    use_host_pipeline: bool = False   # feed batches through the native C++ threaded
                                      # prefetcher (the DataLoader num_workers=4 analog,
                                      # src/train_dist.py:43-45) instead of the device-
                                      # resident scan fast path; same math, same order
    scan_unroll: int = 1              # epoch-scan body unroll factor (semantics-preserving
                                      # codegen knob; amortizes per-step control overhead)
    grad_accum: int = 1               # accumulate gradients over N equal microbatches per
                                      # optimizer step (N× less activation memory; update
                                      # exactly equals the full-batch step — pinned)
    pregather: bool = False           # gather each scan segment's batches once up front
                                      # instead of per step (semantics-preserving; trades
                                      # HBM for per-step gather latency)
    max_train_examples: int = 0       # 0 = full split; >0 truncates (dev/CI shortening —
    max_test_examples: int = 0        # no reference analog; the reference always trains full)


@dataclass(frozen=True)
class DistributedConfig:
    """Knobs of the distributed trainer (reference ``src/train_dist.py:124-139``)."""

    epochs: int = 6                   # src/train_dist.py:139
    global_batch_size: int = 64       # src/train_dist.py:125 (per-worker = global/world, :133)
    batch_size_test: int = 1000       # src/train_dist.py:126
    learning_rate: float = 0.02       # src/train_dist.py:127
    momentum: float = 0.5             # src/train_dist.py:128
    optimizer: str = "sgd"            # 'sgd' (reference parity) or 'adamw'
                                      # (see SingleProcessConfig.optimizer)
    weight_decay: float = 0.0         # AdamW decoupled weight decay (adamw only)
    lr_schedule: str = "constant"     # 'constant' or 'cosine' (see
                                      # SingleProcessConfig.lr_schedule)
    warmup_steps: int = 0             # linear warmup ramp over the first N updates
    clip_grad_norm: float = 0.0       # global-norm gradient clipping; 0 disables
    label_smoothing: float = 0.0      # torch label-smoothing semantics
    ema_decay: float = 0.0            # params EMA in the compiled step (torch
                                      # swa_utils semantics); eval uses EMA weights
    async_checkpoint: bool = False    # background-thread checkpoint writes
    log_interval: int = 10            # src/train_dist.py:129
    seed: int = 1                     # src/train_dist.py:135 (model/init seed)
    sampler_seed: int = 42            # src/train_dist.py:37 (DistributedSampler seed)
    data_dir: str = "files"
    download_data: bool = False       # ≙ torchvision download=True (src/train_dist.py:22-30);
                                      # atomic install makes concurrent fetches by
                                      # co-hosted processes safe (last replace wins)
    results_dir: str = "results"
    images_dir: str = "images"
    shard_eval: bool = False          # False reproduces the reference's every-rank-evaluates-
                                      # the-full-test-set behavior (src/train_dist.py:21-24,
                                      # §2d.7); True shards eval + psums the sums.
    fsdp: bool = False                # ZeRO/FSDP (r5): shard params + optimizer
                                      # state over the SAME data axis the batch is
                                      # sharded on (parallel/fsdp.py) — per-device
                                      # weight+optimizer memory divides by the
                                      # worker count; trajectory identical to
                                      # plain DP (pinned in tests)
    resume_from: str = ""             # full-TrainState checkpoint to resume from (the
                                      # restore path the reference lacks; the distributed
                                      # trainer writes one per epoch to
                                      # results_dir/model_dist.ckpt)
    model: str = "cnn"                # model family: 'cnn' or 'transformer' (see
                                      # SingleProcessConfig.model)
    bf16: bool = False                # bfloat16 activations (see SingleProcessConfig.bf16)
    remat: bool = False               # jax.checkpoint transformer blocks (see
                                      # SingleProcessConfig.remat)
    remat_policy: str = ""            # see SingleProcessConfig.remat_policy
    causal: bool = False              # decoder-style attention (see
                                      # SingleProcessConfig.causal)
    attention_window: int = 0         # sliding-window attention width (see
                                      # SingleProcessConfig.attention_window)
    kv_heads: int = 0                 # grouped-query attention K/V head count (see
                                      # SingleProcessConfig.kv_heads)
    rope: bool = False                # rotary position embeddings (see
                                      # SingleProcessConfig.rope)
    heartbeat_dir: str = ""           # per-process liveness files for the fleet
                                      # supervisor (see SingleProcessConfig); "" off
    handle_preemption: bool = False   # cooperative SIGTERM stop at the next epoch
                                      # boundary, exit 75 (see SingleProcessConfig)
    keep_checkpoints: int = 0         # keep-last-N versioned checkpoint store with
                                      # manifest under results_dir/checkpoints/
                                      # (see SingleProcessConfig); 0 off
    guard: bool = False               # in-step anomaly verdict + guarded identity
                                      # update (see SingleProcessConfig.guard)
    guard_zscore: float = 8.0         # spike threshold (see SingleProcessConfig)
    anomaly_exit: int = 0             # exit 65 "poisoned" once >= N anomalies
                                      # (see SingleProcessConfig); 0 off
    skip_steps: str = ""              # identity-update replay windows "a:b[,c:d]"
                                      # (see SingleProcessConfig.skip_steps)
    host_local_feed: bool = False     # multi-host input pipeline: each process gathers and
                                      # feeds ONLY its addressable devices' shard of every
                                      # batch (SURVEY.md §7 hard part (d)) instead of the
                                      # device-resident replicated dataset + on-device
                                      # gather fast path; same plan, same math
    scan_unroll: int = 1              # epoch-scan body unroll factor (semantics-preserving)
    pregather: bool = False           # whole-epoch up-front batch gather (semantics-
                                      # preserving; trades HBM for per-step gather latency)
    grad_accum: int = 1               # gradient accumulation microbatches per step (see
                                      # SingleProcessConfig.grad_accum)
    profile: bool = False
    profile_dir: str = "results/profile"
    telemetry: str = ""               # structured run-telemetry JSONL path (see
                                      # SingleProcessConfig.telemetry); "" off
    health_stats: bool = False        # in-scan training-health accumulators (see
                                      # SingleProcessConfig.health_stats)
    max_train_examples: int = 0       # 0 = full split; >0 truncates (dev/CI shortening —
    max_test_examples: int = 0        # no reference analog; the reference always trains full)


@dataclass(frozen=True)
class ComposedConfig:
    """Knobs of the composed-parallelism trainer (``train/composed.py`` — beyond-parity:
    the reference has no TP/SP mode to mirror, so defaults are small-demo-sized)."""

    mesh: str = "data=2,seq=2,model=2"  # named axes: data (DP), seq (ring attention),
                                        # model (Megatron TP); product = device count
    plan: str = ""                      # automatic parallelism planning (plan/):
                                        # 'auto' picks mesh/fsdp/microbatch split
                                        # from the analytical cost model, 'tune'
                                        # re-ranks the top candidates by measured
                                        # step time, a path replays a saved plan
                                        # JSON; overrides --mesh/--fsdp/
                                        # --grad-accum/--pipeline-microbatches.
                                        # "" (default) changes nothing
    seq_len: int = 16                   # tokens per image (a seq mesh axis must divide
                                        # it; indivisible 784/seq_len zero-pads the
                                        # pixel stream — see TransformerClassifier)
    flash_attention: bool = False       # route attention through the Pallas flash
                                        # kernels: ring-of-flash when a seq axis > 1 is
                                        # present, single-chip flash otherwise. Needs
                                        # seq_len % (seq_axis_size * 128) == 0.
    pipeline_microbatches: int = 4      # GPipe microbatches per step under a stage
                                        # axis (bubble fraction (S-1)/(M+S-1));
                                        # batch_size must divide by it, and the
                                        # microbatch by the data axis
    pipeline_schedule: str = "gpipe"    # backward formulation under a stage axis:
                                        # 'gpipe' (autodiff through the scan) or
                                        # '1f1b' (custom-VJP reverse ring, stage-
                                        # input-only residuals + in-tick remat —
                                        # parallel/pipeline.py docstring)
    bf16: bool = False                  # bfloat16 activations (f32 master weights;
                                        # see SingleProcessConfig.bf16)
    remat_policy: str = ""              # see SingleProcessConfig.remat_policy
    remat: bool = False                 # jax.checkpoint each block on backward (not
                                        # with a stage axis — the pipeline engine
                                        # applies blocks itself)
    grad_accum: int = 1                 # gradient accumulation microbatches per step
                                        # (see SingleProcessConfig.grad_accum)
    causal: bool = False                # decoder-style (causal) attention over the
                                        # token sequence instead of bidirectional
    attention_window: int = 0           # sliding-window attention width (dense or
                                        # single-chip flash cores only — the ring/
                                        # ulysses SP schedules do not window; 0 off)
    kv_heads: int = 0                   # grouped-query attention K/V head count
                                        # (0 = MHA; must divide the model's 4 heads)
    rope: bool = False                  # rotary position embeddings on q/k
    moe_top_k: int = 1                  # MoE router: 1 = Switch top-1, 2 = GShard
                                        # top-2 (expert axis only)
    zigzag_attention: bool = False      # load-balanced zig-zag causal ring schedule
                                        # (parallel.zigzag_ring_attention); requires
                                        # --causal and seq_len % (2*seq_axis) == 0
    seq_impl: str = "ring"              # sequence-parallel schedule under a seq axis:
                                        # 'ring' (K/V ppermute rotation) or 'ulysses'
                                        # (head-scatter all-to-all,
                                        # parallel.ulysses_attention — needs
                                        # heads % (model_axis*seq_axis) == 0; composes
                                        # with --flash-attention, not
                                        # --zigzag-attention)
    resume_from: str = ""               # full-TrainState checkpoint to resume from;
                                        # checkpoints are layout-standard, so a run
                                        # resumes from ANY mesh's checkpoint (incl.
                                        # across stage layouts via the bridge)
    profile: bool = False               # jax.profiler capture around the epoch loop
    profile_dir: str = "results/profile"
    telemetry: str = ""                 # structured run-telemetry JSONL path (see
                                        # SingleProcessConfig.telemetry); "" off
    health_stats: bool = False          # in-scan training-health accumulators (see
                                        # SingleProcessConfig.health_stats)
    epochs: int = 2
    batch_size: int = 64
    batch_size_test: int = 1000
    learning_rate: float = 0.05
    momentum: float = 0.5
    optimizer: str = "sgd"              # 'sgd' or 'adamw' (see
                                        # SingleProcessConfig.optimizer); composes with
                                        # every mesh incl. stage (moments bridge
                                        # through the stacked layout)
    weight_decay: float = 0.0           # AdamW decoupled weight decay (adamw only)
    lr_schedule: str = "constant"       # 'constant' or 'cosine' (see
                                        # SingleProcessConfig.lr_schedule)
    warmup_steps: int = 0               # linear warmup ramp over the first N updates
    clip_grad_norm: float = 0.0         # global-norm gradient clipping; 0 disables
    label_smoothing: float = 0.0        # torch label-smoothing semantics
    ema_decay: float = 0.0              # params EMA in the compiled step (torch
                                        # swa_utils semantics); eval uses EMA weights
    async_checkpoint: bool = False      # background-thread checkpoint writes
    fsdp: bool = False                  # ZeRO x TP hybrid (r5): params + optimizer
                                        # state additionally shard over the data
                                        # axis on each leaf's largest free dim
                                        # (parallel/fsdp.py::hybrid_state_shardings)
                                        # — memory divides by data x model size;
                                        # trajectory identical (pinned in tests);
                                        # rejected with a stage axis
    dcn_data: int = 0                   # multi-slice: the data axis's leading
                                        # factor spans this many slices/granules
                                        # over DCN (0 = flat single-network mesh);
                                        # all other axes stay on ICI
    sharded_checkpoint: bool = False    # ALSO write a per-process distributed
                                        # checkpoint each epoch (<ckpt>.sharded/:
                                        # every process saves only the shards it
                                        # addresses, no gather); --resume-from
                                        # accepts the directory (not with stage=)
    heartbeat_dir: str = ""             # per-process liveness files for the fleet
                                        # supervisor (see SingleProcessConfig)
    handle_preemption: bool = False     # cooperative SIGTERM stop at the next epoch
                                        # boundary, exit 75 (see SingleProcessConfig)
    keep_checkpoints: int = 0           # keep-last-N versioned checkpoint store with
                                        # manifest (see SingleProcessConfig); 0 off
    guard: bool = False                 # in-step anomaly verdict + guarded identity
                                        # update (see SingleProcessConfig.guard)
    guard_zscore: float = 8.0           # spike threshold (see SingleProcessConfig)
    anomaly_exit: int = 0               # exit 65 "poisoned" once >= N anomalies
                                        # (see SingleProcessConfig); 0 off
    skip_steps: str = ""                # identity-update replay windows "a:b[,c:d]"
                                        # (see SingleProcessConfig.skip_steps)
    dropout_rate: float = 0.0           # 0 keeps composed runs comparable across meshes
    seed: int = 1
    data_dir: str = "files"
    download_data: bool = False
    results_dir: str = "results"
    max_train_examples: int = 0
    max_test_examples: int = 0


@dataclass(frozen=True)
class LMConfig:
    """Knobs of the autoregressive pixel-LM trainer (``train/lm.py`` — beyond-parity:
    the reference has no language model or generation path to mirror)."""

    epochs: int = 2
    batch_size: int = 64                # global batch, sharded over the data axis
    num_levels: int = 16                # gray-level vocabulary (BOS id = num_levels)
    embed_dim: int = 64
    num_layers: int = 2
    num_heads: int = 4
    dropout_rate: float = 0.0
    attention_window: int = 0           # sliding-window (local) causal attention
                                        # width over the pixel stream (0 = full)
    kv_heads: int = 0                   # grouped-query attention: K/V head count
                                        # (0 = MHA; divides num_heads; shrinks the
                                        # decode KV cache num_heads/kv_heads x)
    mesh: str = ""                      # optional named mesh, e.g. "data=2,seq=4"
                                        # or "data=2,model=2": data shards the
                                        # batch (DP), seq runs ring attention over
                                        # the pixel stream (context parallelism —
                                        # the LM is causal, so a seq axis trains
                                        # decoder-style long context), model
                                        # Megatron-shards the block kernels (TP,
                                        # r5; composes with data and seq).
                                        # Empty = all devices on one data axis.
    plan: str = ""                      # automatic parallelism planning (plan/):
                                        # 'auto' | 'tune' | a saved plan JSON
                                        # path; overrides --mesh/--grad-accum
                                        # (data x model search). "" off
    zigzag_attention: bool = False      # use the load-balanced zig-zag causal ring
                                        # schedule on the seq axis (uniform per-hop
                                        # work; needs seq_len % (2*seq_axis) == 0)
    rope: bool = False                  # rotary position embeddings (replaces the
                                        # learned pos_embed; decode rotates its
                                        # position by the same formula)
    learning_rate: float = 1e-3
    momentum: float = 0.5               # sgd only (adamw is the LM default)
    optimizer: str = "adamw"
    weight_decay: float = 0.01
    lr_schedule: str = "constant"
    warmup_steps: int = 0
    clip_grad_norm: float = 1.0         # LM training convention; 0 disables
    label_smoothing: float = 0.0        # torch label-smoothing semantics
    ema_decay: float = 0.0              # params EMA in the compiled step (torch
                                        # swa_utils semantics); eval/generation use
                                        # the EMA weights
    async_checkpoint: bool = False      # background-thread checkpoint writes
    grad_accum: int = 1
    bf16: bool = False
    remat: bool = False
    remat_policy: str = ""              # see SingleProcessConfig.remat_policy
    eval_batch: int = 500               # test-perplexity scan batch (must divide split)
    generate: int = 6                   # sample this many digits after training (0 off)
    temperature: float = 1.0            # sampling temperature (<= 0 decodes greedily)
    top_k: int = 0                      # sample only the k most likely tokens (0 off)
    top_p: float = 1.0                  # nucleus sampling mass cutoff (1.0 off)
    seed: int = 1
    data_dir: str = "files"
    download_data: bool = False
    corpus: str = ""                    # sharded token-corpus directory
                                        # (tools/build_corpus.py output): train on
                                        # its streaming shards instead of MNIST
                                        # pixel streams; seq_len/vocab come from
                                        # corpus.json, the resume cursor from the
                                        # checkpoint manifest (DESIGN.md §26)
    model_config: str = ""              # a published architecture's configuration
                                        # file (models/hybrid_lm.py: layer_types,
                                        # widths, this chip's share of the experts
                                        # and the vocabulary); the model is built
                                        # from it instead of from the pixel LM's
                                        # --embed-dim/--num-layers/... and trains
                                        # on --corpus
    data_throttle_s: float = 0.0        # per-batch streaming-loader brake (debug/
                                        # bench: proves goodput's data_wait is
                                        # actually measured); 0 off
    results_dir: str = "results"
    images_dir: str = "images"
    resume_from: str = ""               # per-epoch checkpoint to resume from
    heartbeat_dir: str = ""             # per-process liveness files for the fleet
                                        # supervisor (see SingleProcessConfig)
    handle_preemption: bool = False     # cooperative SIGTERM stop at the next epoch
                                        # boundary, exit 75 (see SingleProcessConfig)
    keep_checkpoints: int = 0           # keep-last-N versioned checkpoint store with
                                        # manifest (see SingleProcessConfig); 0 off
    guard: bool = False                 # in-step anomaly verdict + guarded identity
                                        # update (see SingleProcessConfig.guard)
    guard_zscore: float = 8.0           # spike threshold (see SingleProcessConfig)
    anomaly_exit: int = 0               # exit 65 "poisoned" once >= N anomalies
                                        # (see SingleProcessConfig); 0 off
    skip_steps: str = ""                # identity-update replay windows "a:b[,c:d]"
                                        # (see SingleProcessConfig.skip_steps)
    telemetry: str = ""                 # structured run-telemetry JSONL path (see
                                        # SingleProcessConfig.telemetry); "" off
    health_stats: bool = False          # in-scan training-health accumulators (see
                                        # SingleProcessConfig.health_stats)
    profile: bool = False               # jax.profiler capture around the epoch loop:
                                        # the loop's `epoch/*` spans beside the device ops
    profile_dir: str = "results/profile"
    max_train_examples: int = 0
    max_test_examples: int = 0


def _add_args(parser: argparse.ArgumentParser, cfg) -> None:
    for f in dataclasses.fields(cfg):
        arg = "--" + f.name.replace("_", "-")
        if f.type == "bool" or isinstance(f.default, bool):
            parser.add_argument(arg, action=argparse.BooleanOptionalAction,
                                default=f.default)
        else:
            parser.add_argument(arg, type=type(f.default), default=f.default)


def parse_config(cls, argv: list[str] | None = None):
    """Build a config of type ``cls`` from CLI args (every field is a ``--flag``)."""
    parser = argparse.ArgumentParser(description=cls.__doc__)
    _add_args(parser, cls)
    ns = parser.parse_args(argv)
    return cls(**{f.name: getattr(ns, f.name) for f in dataclasses.fields(cls)})
