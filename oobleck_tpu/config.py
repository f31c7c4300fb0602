"""Job configuration schema.

Capability match for the reference's config dataclasses
(/root/reference/oobleck/elastic/training_util.py:8-39), re-shaped for TPU:
`num_workers` means worker processes per *host* (a TPU host owns all its local
chips — there is no per-GPU process pinning), and a TPU-specific `execution`
section carries mesh / precision knobs the reference does not have.

Serialization is plain-dict based (yaml / json safe) so configs can travel the
elastic control plane's wire protocol without pickle.
"""

from __future__ import annotations

import dataclasses
from dataclasses import dataclass, field
from typing import Any

import yaml


# What a job that states no `seq_len` trains at, where the model's context
# allows: the length every profile and plan was made at before the job
# could say.
DEFAULT_MAX_SEQ_LEN = 1024


def training_seq_len(model_config, seq_len: int | None = None) -> int:
    """The sequence length a job trains, profiles and draws its data at:
    `seq_len` where the job states one (refused above the model's
    context), else the model's context up to DEFAULT_MAX_SEQ_LEN."""
    context = getattr(model_config, "max_position_embeddings", None)
    if seq_len is None:
        return min(context or DEFAULT_MAX_SEQ_LEN, DEFAULT_MAX_SEQ_LEN)
    if seq_len < 1 or (context is not None and seq_len > context):
        raise ValueError(
            f"job.seq_len={seq_len} is outside the model's context of "
            f"{context} positions")
    return int(seq_len)


@dataclass
class DistributedArguments:
    """Cluster topology and control-plane addressing."""

    master_ip: str = "127.0.0.1"
    master_port: int = 19191
    node_ips: list[str] = field(default_factory=lambda: ["127.0.0.1"])
    node_port: int = 22
    num_workers: int = 1
    num_agents_per_node: int = 1
    username: str | None = None


@dataclass
class JobArguments:
    """Training-run hyperparameters used by the engine and planner."""

    fault_threshold: int = 3
    microbatch_size: int = 8
    global_microbatch_size: int = 128
    steps: int = 50
    learning_rate: float = 1e-4
    warmup_steps: int = 10
    weight_decay: float = 0.01
    max_grad_norm: float = 1.0
    # Tokens of one training sequence. None: the model's context, up to
    # DEFAULT_MAX_SEQ_LEN (`training_seq_len`, the one place with the rule).
    seq_len: int | None = None

    def __post_init__(self) -> None:
        if self.global_microbatch_size % self.microbatch_size != 0:
            raise ValueError(
                "global_microbatch_size must be a multiple of microbatch_size: "
                f"{self.global_microbatch_size} % {self.microbatch_size} != 0"
            )

    @property
    def global_num_microbatch(self) -> int:
        return self.global_microbatch_size // self.microbatch_size


@dataclass
class ModelArguments:
    """Model family + dataset selection.

    `model_name` follows HF naming (e.g. "gpt2", "gpt2-xl") resolved through
    oobleck_tpu.models.registry; `model_args` overrides config fields the same
    way the reference threads them into AutoConfig.
    """

    model_name: str = "gpt2"
    model_tag: str = "default"
    dataset_path: str = "synthetic"
    dataset_name: str | None = None
    model_args: dict[str, Any] = field(default_factory=dict)


@dataclass
class ExecutionArguments:
    """TPU-specific execution knobs (no reference counterpart).

    Every knob here is consumed by the engine:
      * MPMD path: `tensor_parallel`/`sequence_parallel`/`fsdp` factor each
        stage's chips into a (fsdp, seq, tensor) stage mesh; `num_stages`
        filters the feasible pipeline templates; `precision`/`remat`/
        `attention_impl` override model config. Sequence parallelism in a
        stage is Ulysses/ring over the stage-local `seq` axis, so
        long-context and elastic heterogeneous pipelines compose.
      * Fused path (`engine_path: fused`, or `auto` with
        sequence_parallel > 1): one global mesh
        (data, stage, fsdp, seq, tensor) runs the compiled SPMD train step
        (parallel/train.py).
    """

    # Which execution path drives training: "mpmd" (per-stage jits +
    # 1F1B interpreter, supports heterogeneous pipelines), "fused" (one
    # compiled SPMD program over a global mesh), or "auto" (fused when
    # sequence_parallel > 1, mpmd otherwise).
    engine_path: str = "auto"
    # Mesh axis sizes; -1 means "infer".
    num_stages: int = -1          # pipeline-parallel degree (per pipeline)
    tensor_parallel: int = 1      # intra-op model sharding degree
    fsdp: int = -1                # param-sharding degree within a stage (-1: remaining chips)
    sequence_parallel: int = 1    # ring-attention / context-parallel degree
    precision: str = "bfloat16"   # activation/compute dtype
    remat: bool = True            # rematerialize per-layer activations
    attention_impl: str = "auto"  # auto | xla | pallas | ring | ulysses
    checkpoint_dir: str | None = None
    checkpoint_interval: int = 0  # steps; 0 disables
    # Durable-state plane knobs (oobleck_tpu/ckpt). keep_last <= 0 keeps
    # every step; checkpoint_async=False is the synchronous baseline
    # (the train loop stalls for the full device->host->disk write).
    checkpoint_keep_last: int = 3
    checkpoint_async: bool = True
    # Checkpoint-FREE multi-host recovery (reference engine.py:238-309:
    # survivors broadcast live states, no checkpoint reload): each worker
    # mirrors its LOCAL layers' live state to a host-local file every
    # mirror_interval steps; after a failure the respawned world refills
    # every layer from the freshest surviving mirror with one collective,
    # falling back to a checkpoint only for layers no survivor holds.
    # mirror_dir must be HOST-LOCAL storage (e.g. /dev/shm); None disables.
    mirror_dir: str | None = None
    mirror_interval: int = 1
    # Cross-pipeline replica re-broadcast period (steps; 0 disables). DP
    # replicas of a layer drift bitwise over time (different per-mesh
    # reduction orders); the reference re-broadcasts only during failure
    # recovery (_copy_model_states, engine.py:238-309) — here drift is
    # bounded unconditionally, independent of checkpointing.
    replica_sync_interval: int = 100
    # Fraction of the dataset reserved as a held-out tail for evaluate()
    # when no real validation split exists. Nonzero BY DEFAULT so eval is
    # honest out of the box; 0 opts out explicitly (train on everything,
    # the reference behavior — its eval data is never actually driven).
    eval_fraction: float = 0.02
    # Pipeline schedule for the MPMD path: "1f1b" (canonical) or
    # "interleaved" (Megatron-style virtual pipeline — each stage holds
    # virtual_stages model chunks, shrinking the bubble from
    # (S-1)/(M+S-1) to (S-1)/(v*M+S-1)). Interleaving requires the
    # per-pipeline microbatch count to be a multiple of num_stages and at
    # least num_stages*virtual_stages pipeline layers; when a
    # reconfiguration leaves a plan that cannot honor it, the engine falls
    # back to 1f1b and records a flight-recorder event.
    pipeline_schedule: str = "1f1b"
    virtual_stages: int = 1
    # Host loss-readback period (steps). 1 = read every step (the classic
    # contract: per-step log lines, loss gauge per step). N > 1 keeps the
    # loss on-device and resolves N steps at a time, removing the only
    # blocking host sync from the steady-state train loop.
    loss_readback_every: int = 1
    # Bounded-time recovery: how many host losses ahead to AOT-precompile
    # re-planned stage executables for (execution/precompile.py). Depth d
    # walks the plans the instantiator would match after losing 1..d hosts
    # (plus the current plan) and compiles their stage programs into the
    # persistent compilation cache on a background thread, so
    # reconfigure()/respawn deserializes instead of cold-compiling.
    # 0 disables. OOBLECK_PRECOMPILE overrides at runtime.
    precompile_recovery_depth: int = 2
    # Degraded-mode execution plane (oobleck_tpu/degrade): on failure, try
    # rerouting the dead DP replica's microbatches into the survivors'
    # pipeline bubbles BEFORE template re-instantiation — same topology,
    # no re-plan, no recompile (ReCycle, arxiv 2405.14009). A reroute is
    # only taken when the planner projects step-time slowdown <=
    # degrade_max_slowdown; otherwise (or when no DP peer survives) the
    # engine falls back to re-instantiation. OOBLECK_DEGRADE (0/1) and
    # OOBLECK_DEGRADE_MAX_SLOWDOWN override at runtime.
    degrade_enabled: bool = True
    degrade_max_slowdown: float = 4.0
    # Collective/compute overlap on the fused path (parallel/overlap.py):
    # bucketed ppermute-ring grad sync, FSDP gather prefetch, double-buffered
    # cross-stage sends, and XLA async-collective flag passthrough.
    # OOBLECK_OVERLAP, OOBLECK_OVERLAP_BUCKET_MB, OOBLECK_OVERLAP_PREFETCH,
    # OOBLECK_OVERLAP_DB_SENDS, OOBLECK_OVERLAP_XLA_FLAGS override at runtime.
    overlap_enabled: bool = False
    overlap_bucket_bytes: int = 4 * 1024 * 1024
    overlap_prefetch: bool = True
    overlap_db_sends: bool = False
    overlap_xla_flags: bool = True

    def __post_init__(self) -> None:
        if self.engine_path not in ("auto", "mpmd", "fused"):
            raise ValueError(
                f"engine_path must be auto|mpmd|fused, got {self.engine_path!r}"
            )
        if self.attention_impl not in ("auto", "xla", "pallas", "ring",
                                       "ulysses"):
            raise ValueError(
                "attention_impl must be auto|xla|pallas|ring|ulysses, got "
                f"{self.attention_impl!r}"
            )
        if self.pipeline_schedule not in ("1f1b", "interleaved"):
            raise ValueError(
                "pipeline_schedule must be 1f1b|interleaved, got "
                f"{self.pipeline_schedule!r}"
            )
        if self.virtual_stages < 1:
            raise ValueError(
                f"virtual_stages must be >= 1, got {self.virtual_stages}"
            )
        if self.pipeline_schedule == "1f1b" and self.virtual_stages > 1:
            raise ValueError(
                "virtual_stages > 1 requires pipeline_schedule: interleaved"
            )
        if self.loss_readback_every < 1:
            raise ValueError(
                f"loss_readback_every must be >= 1, got "
                f"{self.loss_readback_every}"
            )
        if self.degrade_max_slowdown <= 1.0:
            raise ValueError(
                "degrade_max_slowdown must be > 1 (a reroute always costs "
                f"some step time), got {self.degrade_max_slowdown}"
            )
        if self.overlap_bucket_bytes <= 0:
            raise ValueError(
                f"overlap_bucket_bytes must be > 0, got "
                f"{self.overlap_bucket_bytes}"
            )

    @property
    def resolved_virtual_stages(self) -> int:
        return self.virtual_stages if self.pipeline_schedule == "interleaved" else 1

    def apply_durable_env_overrides(self) -> None:
        """Runtime overrides for the durable-state plane — preemption
        notice handling and checkpoint cadence are deployment properties,
        not model properties, so they must be settable without editing the
        job yaml: OOBLECK_CKPT_DIR, OOBLECK_CKPT_INTERVAL,
        OOBLECK_CKPT_KEEP, OOBLECK_CKPT_ASYNC (0/1)."""
        import os

        v = os.environ.get("OOBLECK_CKPT_DIR")
        if v:
            self.checkpoint_dir = v
        v = os.environ.get("OOBLECK_CKPT_INTERVAL")
        if v:
            self.checkpoint_interval = int(v)
        v = os.environ.get("OOBLECK_CKPT_KEEP")
        if v:
            self.checkpoint_keep_last = int(v)
        v = os.environ.get("OOBLECK_CKPT_ASYNC")
        if v:
            self.checkpoint_async = v.lower() not in ("0", "false", "no")
        v = os.environ.get("OOBLECK_DEGRADE")
        if v:
            self.degrade_enabled = v.lower() not in ("0", "false", "no")
        v = os.environ.get("OOBLECK_DEGRADE_MAX_SLOWDOWN")
        if v:
            self.degrade_max_slowdown = float(v)
        v = os.environ.get("OOBLECK_OVERLAP")
        if v:
            self.overlap_enabled = v.lower() not in ("0", "false", "no")
        v = os.environ.get("OOBLECK_OVERLAP_BUCKET_MB")
        if v:
            self.overlap_bucket_bytes = int(float(v) * 1024 * 1024)
        v = os.environ.get("OOBLECK_OVERLAP_PREFETCH")
        if v:
            self.overlap_prefetch = v.lower() not in ("0", "false", "no")
        v = os.environ.get("OOBLECK_OVERLAP_DB_SENDS")
        if v:
            self.overlap_db_sends = v.lower() not in ("0", "false", "no")
        v = os.environ.get("OOBLECK_OVERLAP_XLA_FLAGS")
        if v:
            self.overlap_xla_flags = v.lower() not in ("0", "false", "no")

    def overlap_config(self):
        """The parallel.overlap.OverlapConfig these arguments describe."""
        from oobleck_tpu.parallel.overlap import OverlapConfig

        return OverlapConfig(
            enabled=self.overlap_enabled,
            bucket_bytes=self.overlap_bucket_bytes,
            prefetch_fsdp=self.overlap_prefetch,
            double_buffer_sends=self.overlap_db_sends,
            xla_flags=self.overlap_xla_flags,
        )

    def resolved_path(self) -> str:
        # auto: fused is still the default home for sequence parallelism
        # (single compiled program); explicit `engine_path: mpmd` +
        # sequence_parallel > 1 runs seq-parallel stage meshes instead.
        if self.engine_path != "auto":
            return self.engine_path
        return "fused" if self.sequence_parallel > 1 else "mpmd"


@dataclass
class ServeArguments:
    """Elastic serving plane knobs (oobleck_tpu/serve).

    The server consumes the durable-state plane's checkpoint root
    (`execution.checkpoint_dir` / OOBLECK_CKPT_DIR) and hot-reloads the
    newest committed step while serving."""

    port: int = 0                 # HTTP port; 0 = ephemeral (tests)
    slots: int = 4                # memory-budget unit: slots * max_seq tokens
    max_seq: int = 256            # per-request length cap (prompt + gen)
    max_queue: int = 64           # bounded admission queue; full -> reject
    reload_secs: float = 5.0      # checkpoint-watcher poll period
    max_tokens_default: int = 64  # per-request cap when unspecified
    # Paged KV cache (serve/kv_blocks.py + ops/paged_attention.py).
    page_size: int = 16           # tokens per KV pool page
    kv_pages: int = 0             # pool pages incl. garbage page; 0 = auto
    #                               (slots * max_seq / page_size — the same
    #                               HBM budget the dense cache would take)
    lanes: int = 0                # paged decode batch width; 0 = auto
    # Speculative multi-token decode (serve/speculative.py).
    # "off" keeps the one-token step; "lookup" = model-free prompt-lookup
    # drafting; "draft" = second-checkpoint draft model (needs
    # spec_draft_root, falls back to lookup without one).
    speculation: str = "off"
    spec_k: int = 4               # max draft tokens per lane per step
    spec_min_accept: float = 0.25  # acceptance EWMA below this -> k=0
    spec_ngram: int = 3           # lookup drafter max n-gram
    spec_probe_every: int = 32    # k=1 probe period for collapsed lanes
    spec_draft_root: str = ""     # draft-model checkpoint root

    def apply_serve_env_overrides(self) -> None:
        """Deployment-property overrides, same contract as the durable
        plane's: OOBLECK_SERVE_PORT, OOBLECK_SERVE_SLOTS,
        OOBLECK_SERVE_RELOAD_SECS,
        OOBLECK_SERVE_PAGE_SIZE, OOBLECK_SERVE_KV_PAGES,
        OOBLECK_SERVE_LANES, OOBLECK_SERVE_SPEC, OOBLECK_SERVE_SPEC_K,
        OOBLECK_SERVE_SPEC_MIN_ACCEPT, OOBLECK_SERVE_SPEC_NGRAM,
        OOBLECK_SERVE_SPEC_PROBE_EVERY, OOBLECK_SERVE_SPEC_DRAFT_ROOT
        are settable without editing job yaml."""
        import os

        v = os.environ.get("OOBLECK_SERVE_PORT")
        if v:
            self.port = int(v)
        v = os.environ.get("OOBLECK_SERVE_SLOTS")
        if v:
            self.slots = int(v)
        v = os.environ.get("OOBLECK_SERVE_RELOAD_SECS")
        if v:
            self.reload_secs = float(v)
        v = os.environ.get("OOBLECK_SERVE_PAGE_SIZE")
        if v:
            self.page_size = int(v)
        v = os.environ.get("OOBLECK_SERVE_KV_PAGES")
        if v:
            self.kv_pages = int(v)
        v = os.environ.get("OOBLECK_SERVE_LANES")
        if v:
            self.lanes = int(v)
        v = os.environ.get("OOBLECK_SERVE_SPEC")
        if v:
            self.speculation = v
        v = os.environ.get("OOBLECK_SERVE_SPEC_K")
        if v:
            self.spec_k = int(v)
        v = os.environ.get("OOBLECK_SERVE_SPEC_MIN_ACCEPT")
        if v:
            self.spec_min_accept = float(v)
        v = os.environ.get("OOBLECK_SERVE_SPEC_NGRAM")
        if v:
            self.spec_ngram = int(v)
        v = os.environ.get("OOBLECK_SERVE_SPEC_PROBE_EVERY")
        if v:
            self.spec_probe_every = int(v)
        v = os.environ.get("OOBLECK_SERVE_SPEC_DRAFT_ROOT")
        if v:
            self.spec_draft_root = v


@dataclass
class OobleckArguments:
    dist: DistributedArguments = field(default_factory=DistributedArguments)
    job: JobArguments = field(default_factory=JobArguments)
    model: ModelArguments = field(default_factory=ModelArguments)
    execution: ExecutionArguments = field(default_factory=ExecutionArguments)
    serve: ServeArguments = field(default_factory=ServeArguments)

    # ---- plain-dict serialization (wire + yaml) ----

    def to_dict(self) -> dict[str, Any]:
        return dataclasses.asdict(self)

    @classmethod
    def from_dict(cls, d: dict[str, Any]) -> "OobleckArguments":
        return cls(
            dist=DistributedArguments(**d.get("dist", {})),
            job=JobArguments(**d.get("job", {})),
            model=ModelArguments(**d.get("model", {})),
            execution=ExecutionArguments(**d.get("execution", {})),
            serve=ServeArguments(**d.get("serve", {})),
        )

    @classmethod
    def from_yaml(cls, path: str) -> "OobleckArguments":
        with open(path) as f:
            return cls.from_dict(yaml.safe_load(f) or {})

    def to_yaml(self, path: str) -> None:
        with open(path, "w") as f:
            yaml.safe_dump(self.to_dict(), f, sort_keys=False)
