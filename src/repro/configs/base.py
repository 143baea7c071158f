"""Config dataclasses for models, shapes, meshes, and training.

Every assigned architecture gets one module in ``repro.configs`` exporting
``CONFIG`` (the exact published configuration) and ``smoke_config()`` (a reduced
same-family config for CPU tests). The registry in ``repro.configs.__init__``
maps ``--arch <id>`` strings to these modules.
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Sequence, Tuple

# ---------------------------------------------------------------------------
# Model configuration
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class MoEConfig:
    """Mixture-of-experts FFN configuration (shared + routed experts)."""

    num_experts: int = 0              # routed experts
    num_shared_experts: int = 0       # always-on experts
    top_k: int = 2
    expert_d_ff: int = 0              # d_ff of each routed expert
    shared_d_ff: int = 0              # total d_ff of the shared expert block
    router_aux_weight: float = 0.001  # load-balance aux loss weight
    first_dense: int = 0              # leading dense (non-MoE) layers
    dense_d_ff: int = 0               # d_ff of those leading dense layers
    capacity_factor: float = 1.25
    # 'tp' shards every expert's d_ff over the model axis (works for any E);
    # 'ep' places E/model_size experts per shard with all-to-all dispatch
    # (requires padded E % model_axis == 0).
    partition_mode: str = "tp"

    @property
    def enabled(self) -> bool:
        return self.num_experts > 0


@dataclasses.dataclass(frozen=True)
class MLAConfig:
    """DeepSeek-V2 multi-head latent attention."""

    kv_lora_rank: int = 512
    q_lora_rank: int = 0              # 0 => full-rank q projection
    qk_rope_dim: int = 64             # per-head rope sub-dimension
    qk_nope_dim: int = 128            # per-head non-rope sub-dimension
    v_head_dim: int = 128

    @property
    def enabled(self) -> bool:
        return self.kv_lora_rank > 0


@dataclasses.dataclass(frozen=True)
class SSMConfig:
    """Mamba/SSD-style state-space head config (used by hymba, rwkv6)."""

    state_dim: int = 16
    conv_dim: int = 4                 # depthwise conv width (mamba)
    expand: int = 2                   # inner dim multiplier
    num_heads: int = 0                # SSD heads (0 => derive)

    @property
    def enabled(self) -> bool:
        return self.state_dim > 0


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    """A single unified model description covering all 10 assigned archs."""

    name: str = "model"
    family: str = "dense"             # dense | moe | vlm | hybrid | ssm | audio
    num_layers: int = 2
    d_model: int = 128
    num_heads: int = 2
    num_kv_heads: int = 2
    head_dim: int = 0                 # 0 => d_model // num_heads
    d_ff: int = 512
    vocab_size: int = 1024
    max_seq_len: int = 8192

    # attention details
    attention_kind: str = "gqa"       # gqa | mla | none (attn-free)
    mla: MLAConfig = MLAConfig(kv_lora_rank=0)
    qk_norm: bool = False
    rope_theta: float = 10000.0
    # sliding-window pattern: window size and the local:global interleave.
    # sliding_window=0 => all layers global. global_every=k => layer i is
    # global iff (i+1) % k == 0 (gemma3's 5 local : 1 global).
    sliding_window: int = 0
    global_every: int = 0
    attn_logit_softcap: float = 0.0

    # ffn
    hidden_act: str = "swiglu"        # swiglu | gelu | relu_sq
    moe: MoEConfig = MoEConfig()

    # alternative token mixers
    ssm: SSMConfig = SSMConfig()      # hybrid/ssm families
    # hymba: parallel attn + ssm heads in the same block
    hybrid_parallel: bool = False

    # rwkv6 specifics
    rwkv_head_dim: int = 64

    # encoder-decoder (whisper)
    is_encoder_decoder: bool = False
    num_encoder_layers: int = 0
    encoder_seq_len: int = 1500      # whisper's fixed 30s -> 1500 frames

    # multimodal stubs: number of prefix embedding positions supplied
    # pre-computed by the (stubbed) frontend; 0 disables.
    num_prefix_embeds: int = 0

    use_bias: bool = False
    tie_embeddings: bool = False
    embed_scale: float = 1.0          # gemma multiplies embeds by sqrt(d)
    norm_eps: float = 1e-6
    dtype: str = "bfloat16"           # activation/param dtype for dry-runs
    vocab_pad_multiple: int = 128

    # remat policy for the scanned blocks: 'none'|'full'|'dots'
    remat: str = "full"

    @property
    def resolved_head_dim(self) -> int:
        return self.head_dim or (self.d_model // max(self.num_heads, 1))

    @property
    def padded_vocab(self) -> int:
        m = self.vocab_pad_multiple
        return ((self.vocab_size + m - 1) // m) * m

    @property
    def q_per_kv(self) -> int:
        return self.num_heads // max(self.num_kv_heads, 1)

    def param_count(self) -> int:
        """Analytic parameter count (used for 6ND model-flops accounting)."""
        from repro.models import registry  # local import to avoid cycles

        return registry.param_count(self)

    def active_param_count(self) -> int:
        from repro.models import registry

        return registry.param_count(self, active_only=True)


# ---------------------------------------------------------------------------
# Input shapes (the four assigned shape cells)
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class ShapeConfig:
    name: str
    seq_len: int
    global_batch: int
    kind: str                         # train | prefill | decode

    @property
    def is_train(self) -> bool:
        return self.kind == "train"


SHAPES: Tuple[ShapeConfig, ...] = (
    ShapeConfig("train_4k", 4_096, 256, "train"),
    ShapeConfig("prefill_32k", 32_768, 32, "prefill"),
    ShapeConfig("decode_32k", 32_768, 128, "decode"),
    ShapeConfig("long_500k", 524_288, 1, "decode"),
)

SHAPES_BY_NAME = {s.name: s for s in SHAPES}


# ---------------------------------------------------------------------------
# Mesh / distribution configuration
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class MeshConfig:
    """Logical mesh description. axes are named; 'pod' optional."""

    shape: Tuple[int, ...] = (16, 16)
    axes: Tuple[str, ...] = ("data", "model")

    @property
    def num_devices(self) -> int:
        n = 1
        for s in self.shape:
            n *= s
        return n

    @property
    def data_axes(self) -> Tuple[str, ...]:
        return tuple(a for a in self.axes if a in ("pod", "data"))

    @property
    def model_axis_size(self) -> int:
        return dict(zip(self.axes, self.shape)).get("model", 1)

    @property
    def data_parallel_size(self) -> int:
        d = dict(zip(self.axes, self.shape))
        return d.get("pod", 1) * d.get("data", 1)


SINGLE_POD_MESH = MeshConfig((16, 16), ("data", "model"))
MULTI_POD_MESH = MeshConfig((2, 16, 16), ("pod", "data", "model"))


# ---------------------------------------------------------------------------
# Aggregation / training configuration (the paper's knobs)
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class AggregationConfig:
    """The paper's Sync/Async/backup-worker policy knobs.

    Strategies are constructed by ``repro.core.registry.get_strategy``:
      'full_sync'  — paper's plain Sync-Opt (wait for all N+b == all workers)
      'backup'     — paper's Alg. 3/4: aggregate first N of N+b, drop b
      'timeout'    — paper §6 future-work variant: aggregate all arrivals
                     within deadline_s of the first (>=1 always aggregated)
      'async'      — paper's Alg. 1/2 baseline (event-driven)
      'softsync'   — Zhang et al. (2015b) related-work baseline: async apply
                     every c arrivals (stale allowed) — for comparisons only
      'staleness'  — paper §2.1 controlled rig: serial SGD applying the
                     gradient from staleness_tau steps ago
      'dynamic_backup' — Dynamic Backup Workers (arXiv:2102.06280):
                     backup strategy whose cutoff N adapts online from
                     the measured straggler tail (docs/robustness.md)
    """

    strategy: str = "backup"
    num_workers: int = 16             # N
    backup_workers: int = 0           # b  (total launched = N + b)
    deadline_s: float = 0.0           # timeout strategy
    softsync_c: int = 1
    # dynamic_backup strategy (arXiv:2102.06280): adapt the aggregate-
    # first-N cutoff online from the measured straggler tail. window =
    # steps of arrival history kept; min_workers = smallest N the
    # controller may choose (0 => max(1, num_workers // 2)).
    dynamic_window: int = 32
    dynamic_min_workers: int = 0
    # where dynamic_backup's adaptation window comes from: 'sim' (the
    # straggler simulator's arrival model) or 'measured' (fenced
    # wall-clock per-worker step times fed by the trainer — see
    # docs/observability.md; host straggler backend only)
    latency_source: str = "sim"
    staleness_tau: int = 0            # staleness strategy: target tau
    staleness_ramp_steps: int = 0     # ramp tau up over the first steps
    staleness_jitter: int = 0         # +- uniform jitter on tau
    # gradient compression over the wire: 'none' | 'bf16' | 'int8_ef'
    compression: str = "none"
    # reduce-scatter + ZeRO-1 instead of all-reduce + replicated opt state
    zero1: bool = False

    @property
    def total_workers(self) -> int:
        return self.num_workers + self.backup_workers


@dataclasses.dataclass(frozen=True)
class OptimizerConfig:
    name: str = "rmsprop_momentum"    # paper's optimizer for Inception
    learning_rate: float = 0.045
    # paper's rule-of-thumb: lr scales linearly with N (A.3: 0.045*N)
    scale_lr_with_workers: bool = True
    decay: float = 0.9                # rmsprop decay
    momentum: float = 0.9
    eps: float = 1e-8
    beta1: float = 0.9                # adam
    beta2: float = 0.999
    weight_decay: float = 0.0
    # exponential schedule gamma0 * beta^(t*N/(2T)) (paper A.2/A.3)
    lr_decay_rate: float = 0.94
    steps_per_epoch: int = 0          # T = |X|/B; 0 disables the schedule
    # linear anneal to 0 over [linear_anneal_from, linear_anneal_steps]
    # (paper A.1 MNIST recipe); >0 takes precedence over the exponential
    linear_anneal_steps: int = 0
    linear_anneal_from: int = 0
    warmup_steps: int = 0
    clip_global_norm: float = 0.0     # >0 enables (async needs it; sync not)
    ema_decay: float = 0.9999         # paper evaluates on EMA of params


@dataclasses.dataclass(frozen=True)
class ExecutionConfig:
    """How the W coordination workers are executed.

    'sim'  — single-device simulation: workers are contiguous row blocks
             of one global batch on one device (every PR-1/PR-3 path).
    'spmd' — the SPMD execution engine (repro.distributed.spmd_engine):
             workers are laid out over a real mesh 'data' axis via
             shard_map, per-worker gradients live on their shard, and
             masked aggregation is a collective (in-shard backup_reduce
             + psum) — no stacked [W, ...] gradient tree ever exists on
             one device. With mesh_model > 1 params, optimizer state and
             EMA are additionally SHARDED over the mesh 'model' axis and
             each worker's gradient is computed tensor-parallel inside
             its 'data' shard (explicit psums over 'model' at the
             contracted dims — sharding.tp_plan decides which groups
             shard; indivisible configs fall back to a carried,
             replicated axis with a warning). Strategies advertise
             support via ``registry.supports_spmd`` (TP opt-out:
             ``spmd_tp_supported = False``); unsupported strategies fall
             back to 'sim' with a warning.
    """

    backend: str = "sim"              # 'sim' | 'spmd'
    mesh_data: int = 1                # 'data' axis size (devices); W % it == 0
    mesh_model: int = 1               # 'model' (tensor-parallel) axis size
    # in-shard reduce: the kernels/backup_reduce Pallas kernel (True) or
    # the jnp reference reduction (False). None = auto: the kernel on
    # TPU (where it runs natively), the jnp dot elsewhere — interpret-
    # mode Pallas is pure overhead off-TPU (docs/spmd.md, BENCH_spmd)
    use_kernel: Optional[bool] = None
    # per-worker gradient batching inside each 'data' shard: 0 = vmap ALL
    # local workers (one fused program, the fast path when activation
    # memory allows), 1 = sequential lax.map (one worker's activations at
    # a time), k = microbatches of k vmapped workers (k must divide
    # total_workers / mesh_data — validated with a structured error)
    grad_batch: int = 0
    # fused bucketed reduce-then-psum (kernels/bucketed_reduce): lanes of
    # the flattened gradient per collective. 0 = one bucket (a single
    # psum carries gradient + monitoring scalars); >0 cuts the flatten
    # into fixed-size buckets whose psums overlap the remaining reduce
    # compute under the latency-hiding XLA recipe (docs/spmd.md)
    bucket_size: int = 0

    @property
    def num_devices(self) -> int:
        return self.mesh_data * self.mesh_model


@dataclasses.dataclass(frozen=True)
class CheckpointConfig:
    directory: str = "checkpoints"
    every_steps: int = 100
    keep: int = 3
    async_save: bool = False
    # self-healing writes (docs/robustness.md): failed saves retry up to
    # write_retries times with seeded-jittered exponential backoff —
    # capped at retry_max_backoff_s, scaled by uniform [1, 1+retry_jitter]
    # — before the error propagates (where the supervisor takes over)
    write_retries: int = 3
    retry_backoff_s: float = 0.01
    retry_max_backoff_s: float = 0.25
    retry_jitter: float = 0.5


@dataclasses.dataclass(frozen=True)
class FaultConfig:
    """Seeded fault injection + recovery supervision (docs/robustness.md).

    ``spec`` is a chaos-plan string parsed by ``repro.core.faults``
    (e.g. ``"crash@10:w1,slowdown@20:w2,ckpt_io@25,preempt@35"`` or
    ``"crash=2,slowdown=3"`` for seeded-random placement). ``seed`` is
    the fault stream's own seed — independent of ``TrainConfig.seed`` so
    the same training run can be replayed under different chaos.
    ``supervise`` routes the run through
    ``repro.train.supervisor.run_supervised`` (crash recovery from the
    last verified-good checkpoint, bounded by ``max_restarts``).
    """

    spec: str = ""
    seed: int = 0
    supervise: bool = False
    max_restarts: int = 3


@dataclasses.dataclass(frozen=True)
class TrainConfig:
    model: ModelConfig = ModelConfig()
    shape: ShapeConfig = SHAPES_BY_NAME["train_4k"]
    mesh: MeshConfig = SINGLE_POD_MESH
    aggregation: AggregationConfig = AggregationConfig()
    optimizer: OptimizerConfig = OptimizerConfig()
    checkpoint: CheckpointConfig = CheckpointConfig()
    execution: ExecutionConfig = ExecutionConfig()
    faults: FaultConfig = FaultConfig()
    seed: int = 0
    total_steps: int = 1000
    log_every: int = 10
    microbatch: int = 0               # 0 => derive from shape & mesh
    # fused chunked loop: iterations per device dispatch. 1 = legacy
    # per-step (mask) / per-arrival (event) path; >1 fuses K iterations —
    # SPMD steps for mask strategies, PS updates for event strategies —
    # into one lax.scan with chunk boundaries forced at checkpoint /
    # kill-injection / rescale steps.
    chunk_size: int = 1
    # mask strategies only (event arrivals are always host-scheduled):
    # 'host'   — numpy straggler streams, bit-exact with the legacy path
    # 'device' — jax.random sampling + select_jax inside the scan body
    #            (distribution-equivalent, zero host work per step)
    straggler_backend: str = "host"
    # ChunkPrefetcher look-ahead: how many upcoming chunks are built on
    # the background thread while the device runs the current dispatch
    # (1 = classic double buffering; generation is pure in (cfg, step),
    # so deeper speculation never changes the batches)
    prefetch_depth: int = 1


def replace(cfg, **kw):
    """dataclasses.replace passthrough (ergonomic alias)."""
    return dataclasses.replace(cfg, **kw)
