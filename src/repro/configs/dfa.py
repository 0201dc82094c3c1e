"""The paper's own DFA system configuration (defaults = Tofino deployment).

PAPER      — faithful Tofino-scale config: 2^17 flows/shard, 10-entry ring,
             64 B payload, 20 ms monitoring period. At this scale the ring
             region is ~84 MB/shard, so gather_variant="auto" resolves to
             the HBM-resident path (ring stays in HBM, XLA gathers the
             routed rows, VMEM holds only double-buffered report tiles).
REDUCED    — CPU-testable miniature with the same structure; its ring
             region fits VMEM, so auto resolves to the full kernel.
"""
import dataclasses

from repro.configs.base import DFAConfig

PAPER = DFAConfig(
    gather_variant="auto",     # budget heuristic -> "hbm" at 2^17 flows
    vmem_budget_mb=16,         # TPU v4/v5e per-core VMEM
)

REDUCED = DFAConfig(
    flows_per_shard=256,
    history=10,
    payload_words=16,
    feature_words=8,
    monitoring_period_us=20_000,
    logstar_bits=7,
    event_block=128,
    report_capacity=128,
    derived_dim=96,
    flow_tile=64,
    gather_variant="auto",     # budget heuristic -> "full" at 256 flows
    vmem_budget_mb=16,
    event_tile=64,             # multiple event tiles per 128-event block
)

# REDUCED shapes forced onto the Tofino-scale memory strategy: the
# equivalence suite / benchmarks use this to exercise the HBM-resident path
# without allocating a 2^17-flow ring.
REDUCED_HBM = dataclasses.replace(REDUCED, gather_variant="hbm")

# REDUCED with the software-pipelined streaming driver: period t's enrich
# half overlaps period t+1's ingest half (run_periods_overlapped).
REDUCED_OVERLAP = dataclasses.replace(REDUCED, overlap_periods=True)

# ... and with the immediate-inference hook armed: enriched features feed
# a linear verdict head (models.registry.get_flow_head) inside the same
# scan body — the paper's "features land on the accelerator and are
# consumed in the same monitoring period" headline, end to end.
REDUCED_INFER = dataclasses.replace(REDUCED, overlap_periods=True,
                                    inference_head="linear",
                                    inference_classes=8)

# REDUCED scaled to the 2D (pod, shard) mesh: flow homes are hashed into
# the global ring keyspace (flow_home="hash"), each pod owns a disjoint
# set of reporter ports (2 per pod here), and report delivery is the
# two-stage intra-pod/cross-pod exchange. Pair with
# launch.mesh.make_dfa_mesh(pods=2, ...); reporter tables are pinned to
# 128 slots per port so the merged reporter state is independent of how
# the mesh factors the same port set.
REDUCED_MULTIPOD = dataclasses.replace(
    REDUCED,
    flow_home="hash",
    pods=2,
    ports_per_pod=2,
    reporter_slots=128,
    flows_per_shard=128,
    port_report_capacity=32,
)

# REDUCED_MULTIPOD under the widened V2 wire schema (u16 reporter_id /
# seq — repro.core.wire.V2): the same 2D mesh structure with the 256-port
# cap lifted. The per-port shapes shrink so wide-port meshes (hundreds of
# virtual ports per device) stay CPU-testable; the V2 differential suite
# overrides ports_per_pod per grid point.
REDUCED_MULTIPOD_V2 = dataclasses.replace(
    REDUCED_MULTIPOD,
    wire_format="v2",
    reporter_slots=8,
    flows_per_shard=2048,
    port_report_capacity=2,
)
