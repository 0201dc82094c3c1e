"""Configuration dataclasses for the repro framework.

Every assigned architecture is expressed as a ``ModelConfig``; sub-family
options (MoE, MLA, SSM, hybrid schedule, encoder/decoder, modality stubs)
are nested optional dataclasses so a single registry can instantiate all ten
architectures plus reduced smoke variants.
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass, field
from typing import Any, Optional, Tuple


@dataclass(frozen=True)
class MoEConfig:
    """Mixture-of-experts block configuration (GShard-style top-k routing)."""

    num_experts: int
    top_k: int
    d_ff_expert: int                  # per-expert FFN hidden width
    num_shared_experts: int = 0       # always-on experts (deepseek-v3 style)
    d_ff_shared: int = 0              # hidden width of the shared expert(s)
    capacity_factor: float = 1.25     # per-expert buffer slack for dispatch
    router_dtype: str = "float32"
    # Layers [0, first_moe_layer) use a dense FFN of width ``d_ff_dense``.
    first_moe_layer: int = 0
    d_ff_dense: int = 0
    # deepseek-v3 routing details
    routed_scaling_factor: float = 1.0
    score_func: str = "softmax"       # "softmax" | "sigmoid" (deepseek-v3)
    moe_every: int = 1                # MoE FFN every k-th layer (llama4: 1)


@dataclass(frozen=True)
class MLAConfig:
    """Multi-head Latent Attention (deepseek-v3)."""

    q_lora_rank: int = 1536
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128


@dataclass(frozen=True)
class SSMConfig:
    """Mamba2/SSD block configuration (zamba2) or RWKV6 time-mix options."""

    state_dim: int = 64               # N — SSM state size per head
    head_dim: int = 64                # P — channels per head
    expand: int = 2                   # d_inner = expand * d_model
    conv_width: int = 4               # causal conv1d kernel size
    chunk_size: int = 128             # SSD chunked-scan block length
    n_groups: int = 1                 # B/C groups (mamba2)


@dataclass(frozen=True)
class HybridConfig:
    """Hybrid block schedule (zamba2: Mamba2 trunk + shared attention)."""

    attn_every: int = 6               # full attention block every k layers
    shared_attn: bool = True          # attention blocks share one weight set
    num_shared_blocks: int = 2        # zamba2 has 2 alternating shared blocks


@dataclass(frozen=True)
class EncDecConfig:
    """Encoder-decoder split (whisper). The conv frontend is a STUB: the
    data pipeline / input_specs provide precomputed frame embeddings."""

    num_encoder_layers: int = 4
    num_frames: int = 1500            # whisper 30 s @ 50 Hz after conv stride 2


@dataclass(frozen=True)
class VisionStubConfig:
    """VLM frontend stub (llava-next). input_specs provide precomputed patch
    embeddings already projected to d_model; anyres tiling is upstream."""

    num_patches: int = 2880           # anyres 5 tiles x 576 patches
    patch_embed_dim: int = 0          # 0 => already projected to d_model


@dataclass(frozen=True)
class ModelConfig:
    """A single architecture. Families:

    dense   — decoder-only transformer (GQA/MQA/MHA)
    moe     — decoder-only with MoE FFN (optionally MLA attention)
    hybrid  — Mamba2 trunk with interleaved (shared) attention blocks
    ssm     — attention-free (rwkv6)
    encdec  — encoder-decoder (whisper)
    vlm     — decoder-only with vision-prefix stub (llava-next)
    """

    name: str
    family: str
    num_layers: int
    d_model: int
    num_heads: int
    num_kv_heads: int
    d_ff: int
    vocab_size: int
    head_dim: int = 0                 # 0 => d_model // num_heads
    qkv_bias: bool = False
    qk_norm: bool = False
    rope_theta: float = 10000.0
    norm_eps: float = 1e-5
    tie_embeddings: bool = False
    act: str = "silu"                 # FFN activation (gated)
    moe: Optional[MoEConfig] = None
    mla: Optional[MLAConfig] = None
    ssm: Optional[SSMConfig] = None
    hybrid: Optional[HybridConfig] = None
    encdec: Optional[EncDecConfig] = None
    vision: Optional[VisionStubConfig] = None
    mtp_depth: int = 0                # multi-token-prediction heads (deepseek)
    # numerics / memory policy
    dtype: str = "bfloat16"           # activation/param compute dtype
    param_dtype: str = "bfloat16"
    opt_state_dtype: str = "float32"  # bf16 for XXL models to fit HBM
    remat: str = "full"               # "none" | "full" — scan remat policy
    loss_chunk: int = 2048            # sequence chunk for CE loss (memory)
    attn_chunk: int = 1024            # KV chunk for online-softmax attention
    # provenance
    source: str = ""

    @property
    def resolved_head_dim(self) -> int:
        return self.head_dim if self.head_dim else self.d_model // self.num_heads

    def replace(self, **kw) -> "ModelConfig":
        return dataclasses.replace(self, **kw)


@dataclass(frozen=True)
class DFAConfig:
    """The paper's own system configuration (Table I / Figs 2, 4).

    Defaults mirror the Tofino deployment: 2^17 flows per pipeline shard,
    10-entry history ring, 64 B RoCEv2 payload (45 B Marina vector + pad),
    20 ms monitoring period target.
    """

    flows_per_shard: int = 1 << 17        # 131,072 — classification table size
    history: int = 10                      # Fig 4 ring depth
    payload_words: int = 16                # 64 B / 4 B words (RoCEv2 pow-2 pad)
    feature_words: int = 8                 # 8 x 4 B Table-I statistics
    monitoring_period_us: int = 20_000     # 20 ms target interval
    logstar_bits: int = 7                  # mantissa bits kept by the log* LUT
    counter_bits: int = 8                  # per-flow history counter (paper: 8b)
    seq_check: bool = True                 # per-reporter sequence ids (sec VI-B)
    event_block: int = 1024                # packet events per extraction block
    report_capacity: int = 4096            # max reports routed per step/shard
    derived_dim: int = 96                  # Marina-style derived feature count
    flow_tile: int = 512                   # kernel flow-block tile
    # kernel implementation selection: "auto" | "ref" | "pallas" |
    # "interpret" — see repro.kernels.dispatch (REPRO_KERNEL_BACKEND env
    # var overrides this field; an explicit backend= argument beats both)
    kernel_backend: str = "auto"
    # wire schema version (repro.core.wire registry): "v1" = the paper's
    # bit-faithful 8-bit reporter_id/seq layout (256-port cap, every
    # committed golden); "v2" = widened u16 fields lifting the port/seq
    # caps. REPRO_WIRE_FORMAT env var overrides this field; unknown
    # names fail loud at DFASystem construction.
    wire_format: str = "v1"
    # gather_enrich memory strategy: "auto" | "full" (ring region pinned
    # in VMEM) | "hbm" (ring stays HBM-resident, XLA gathers the rows).
    # auto = VMEM-budget heuristic in dispatch.resolve_gather_variant;
    # REPRO_GATHER_VARIANT env var overrides this field.
    gather_variant: str = "auto"
    # per-core VMEM the auto heuristic may plan against (TPU v4/v5e have
    # ~16 MB; the full-block kernel is chosen only while its ring region
    # + tile working set fit under this)
    vmem_budget_mb: int = 16
    # ingest_update event-stream strategy: "auto" | "block" (sorted event
    # stream streams through BlockSpec-tiled VMEM) | "hbm" (stream stays
    # HBM-resident, per-event_tile double-buffered DMA — events/shard can
    # grow to 2^20 with VMEM = O(event_tile)). auto = VMEM-budget
    # heuristic in dispatch.resolve_ingest_variant; REPRO_INGEST_VARIANT
    # env var overrides this field.
    ingest_variant: str = "auto"
    # sorted-event tile the fused ingest kernels process per grid step;
    # clamped to 256 (the u16-half matmul exactness bound) and to the
    # block's event count
    event_tile: int = 256
    # streaming driver: software-pipeline the period stream so period t's
    # enrich(+inference) half runs in the same scan body as period t+1's
    # ingest half (pipeline.run_periods_overlapped); False = strictly
    # sequential per-period chain (pipeline.run_periods). Output-identical
    # by construction — the knob trades enrich latency out of the ingest
    # budget.
    overlap_periods: bool = False
    # optional inference head applied to the (R, derived_dim) enriched
    # features inside the enrich half: "none" | "linear" | "mlp" (built
    # from models.registry.get_flow_head unless the caller passes its own
    # infer_fn to DFASystem)
    inference_head: str = "none"
    inference_classes: int = 8         # verdict classes the head emits
    inference_hidden: int = 64         # mlp hidden width (linear ignores)
    # -- multi-pod (pod, shard) mesh streaming ---------------------------
    # how a flow's home collector ring is chosen:
    #   "ingest" — legacy 1D scheme: flow ids are minted from the ingest
    #              shard's range (shard * flows_per_shard + slot), so every
    #              report's home IS its ingest shard (the all_to_all is an
    #              identity permutation);
    #   "hash"   — mesh-shape-independent scheme: flow id = FNV-1a hash of
    #              the stored five-tuple into the GLOBAL ring keyspace
    #              (n_devices * flows_per_shard), home device = range shard
    #              of that id (pod-major), delivery is two-stage
    #              (intra-pod all_to_all over shard, then a cross-pod
    #              exchange over pod). A flow observed on ANY port lands in
    #              exactly one ring, which is what makes the (pod, shard)
    #              factorization of the mesh invisible in the merged state.
    #   "rendezvous" — elastic scheme: highest-random-weight hashing over
    #              the ``home_nodes`` roster; flow id = node_id *
    #              flows_per_shard + slot hash. A pod join/leave re-homes
    #              only the affected node's ~1/pods of flows (HRW
    #              restriction property) instead of reshuffling the whole
    #              range-sharded keyspace.
    flow_home: str = "ingest"
    # pod axis size ``launch.mesh.make_dfa_mesh`` builds the mesh with
    # (the mesh, not this field, is authoritative inside DFASystem)
    pods: int = 1
    # reporter ports per pod; 0 = one port per shard device (legacy).
    # total_ports = mesh_pods * ports_per_pod must be a multiple of the
    # device count — each device hosts total_ports / n_devices independent
    # per-port Marina tables, so the merged reporter state depends only on
    # the port set, never on how ports pack onto devices.
    ports_per_pod: int = 0
    # per-PORT Marina classification-table size; 0 = flows_per_shard.
    # Splitting this from flows_per_shard lets the collector ring space
    # (flows_per_shard per device) shrink as the mesh grows while every
    # port's table — and therefore its report stream — stays fixed.
    reporter_slots: int = 0
    # per-PORT due-report capacity; 0 = report_capacity // total_ports
    port_report_capacity: int = 0
    # stage-2 (cross-pod) exchange strategy:
    #   "padded" — worst-case fixed-capacity buckets (every committed
    #              golden; structurally drop-free)
    #   "ragged" — compact per-destination segments: pod-local reports
    #              never enter the exchange, remote reports are
    #              pre-merged flow-major at the source and only
    #              ``crosspod_capacity`` rows per destination pod cross
    #              the scarce inter-pod link. Bitwise-identical to
    #              "padded" at auto capacity (see crosspod_capacity);
    #              adds crosspod_sent/crosspod_messages metrics.
    crosspod_exchange: str = "padded"
    # per-destination-pod segment rows for the ragged exchange; 0 = the
    # worst-case stage-2 capacity (shards_per_pod x stage-1 bucket), at
    # which compaction cannot drop and the ragged path is bitwise ≡ the
    # padded one. Smaller values trade exchange volume for counted
    # bucket_drops — DTA's lossy-telemetry trade, now on the pod link.
    crosspod_capacity: int = 0
    # tuned-config registry JSON consulted by kernels.dispatch before
    # its VMEM heuristics ("" = off; REPRO_TUNING_REGISTRY env var
    # overrides). Produced by the *_scaling.py sweeps' --tune flag.
    tuning_registry: str = ""
    # -- elastic operations (launch.elastic) -----------------------------
    # logical node roster for flow_home="rendezvous": one stable node id
    # per mesh device (pod-major, strictly increasing); () = 0..n_devices-1.
    # HRW homes flows onto node IDS, so removing a pod shrinks the roster
    # without renumbering survivors — their flows (and ring state) stay put.
    home_nodes: Tuple[int, ...] = ()
    # snapshot the full DFAState every N completed periods (0 = never);
    # the replay window after a pod loss is at most this many periods
    snapshot_every_periods: int = 0
    # where stream()/ServingLoop write snapshots ("" = caller must pass
    # a directory explicitly to enable snapshotting)
    snapshot_dir: str = ""
    # keep-last-k snapshot GC (checkpoint.save's ``keep``)
    snapshot_keep: int = 3
    # -- continuous online serving (launch.serving) ----------------------
    # offered event rate the trace-replay source feeds the serving loop,
    # in events/second across the whole mesh; 0 = line rate (exactly one
    # full event batch per period, no queueing)
    serve_offered_eps: float = 0.0
    # per-period latency budget (the SLO) in µs; 0 = monitoring_period_us
    serve_budget_us: int = 0
    # host-side ingest queue capacity in events, on top of the in-flight
    # period batch; 0 = no carry-over queue (arrivals beyond one batch
    # are dropped the period they arrive — per-period drop accounting is
    # then exact by construction)
    serve_queue_events: int = 0
    # which events to shed when arrivals overflow the host queue:
    #   "newest" — tail drop: the just-arrived events are discarded
    #   "oldest" — head drop: evict the oldest queued events to admit
    #              the new ones (freshness-biased telemetry)
    drop_policy: str = "newest"
    # -- transport fault injection (data.faults) -------------------------
    # optional data.faults.FaultSpec applied between translation and
    # collector ingest (the lossy RDMA segment). Typed Any so configs
    # stays import-light; FaultSpec is frozen, keeping the config
    # hashable/jit-static. None = fault path compiled out entirely.
    fault_spec: Optional[Any] = None
    # what launch.elastic does when re-homing hits an unsplittable ring
    # slot (two live flows in one slot with different HRW winners):
    #   "fail" — raise with the collision count (default: fail loud)
    #   "warn" — count + warnings.warn, move the slot by its first entry
    rehome_collision_policy: str = "fail"

    def serve_budget_resolved_us(self) -> int:
        """The serving loop's per-period SLO (falls back to the paper's
        monitoring period)."""
        return self.serve_budget_us or self.monitoring_period_us

    def reporter_table_slots(self) -> int:
        """Per-port Marina table size (falls back to flows_per_shard)."""
        return self.reporter_slots or self.flows_per_shard

    def ring_region_bytes(self) -> int:
        """Shard-local collector ring region footprint (entries+validity)."""
        return self.flows_per_shard * self.history * (
            self.payload_words * 4 + 4)

    def total_flows(self, shards: int) -> int:
        return self.flows_per_shard * shards


@dataclass(frozen=True)
class TrainConfig:
    """Training-driver configuration."""

    learning_rate: float = 3e-4
    warmup_steps: int = 100
    total_steps: int = 1000
    weight_decay: float = 0.1
    beta1: float = 0.9
    beta2: float = 0.95
    eps: float = 1e-8
    grad_clip: float = 1.0
    grad_accum: int = 1
    seed: int = 0
    # fault tolerance
    checkpoint_dir: str = "/tmp/repro_ckpt"
    checkpoint_every: int = 50
    keep_checkpoints: int = 3
    async_checkpoint: bool = True
    # distributed optimization
    grad_compression: str = "none"    # "none" | "int8_ef"
    donate_state: bool = True


@dataclass(frozen=True)
class MeshConfig:
    """Logical mesh description; the production meshes are fixed."""

    shape: Tuple[int, ...] = (16, 16)
    axes: Tuple[str, ...] = ("data", "model")

    @property
    def multi_pod(self) -> bool:
        return "pod" in self.axes
