"""DFA Collector — device-resident telemetry sink (§III-C/IV-C, Fig 4).

The collector exposes a (flows × history × 16-word) memory region living in
accelerator memory; payloads are placed VERBATIM at the translator-computed
coordinates (the GPUDirect analogue: producer-computed placement, no host
mediation, no copies — we even alias the buffer in-place via donation).

Integrity: per-entry checksum (Fig 4) and per-reporter sequence continuity
(the paper's §VI-B recommendation) are validated on ingest; violations are
counted, never crash the path. All layout facts — meta-word field
positions, the reporter-id space sizing ``last_seq``, the seq wrap mask
and the dup-detection window — come off the active
:class:`repro.core.wire.WireFormat`.
"""
from __future__ import annotations

from typing import Any, Dict, NamedTuple, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import DFAConfig
from repro.core import protocol as PROTO
from repro.core import wire as WIRE

Tree = Any
# V1's 8-bit reporter-id space, kept as a module alias for the callers
# that predate the schema; sizing decisions should use wire.n_reporters.
N_REPORTERS = WIRE.V1.n_reporters


class CollectorState(NamedTuple):
    memory: jax.Array      # (F, H, 16) u32 — Fig 4 region
    entry_valid: jax.Array  # (F, H) bool — which ring entries hold data
    last_seq: jax.Array    # (wire.n_reporters,) u32 — seq continuity (VI-B)
    bad_checksum: jax.Array   # () u32
    seq_anomalies: jax.Array  # () u32
    received: jax.Array    # () u32 — total accepted payloads
    lost_reports: jax.Array   # () u32 — seq gaps: sent-but-never-landed


def init_state(cfg: DFAConfig) -> CollectorState:
    F, H = cfg.flows_per_shard, cfg.history
    wf = WIRE.resolve(cfg)
    return CollectorState(
        memory=jnp.zeros((F, H, PROTO.PAYLOAD_WORDS), jnp.uint32),
        entry_valid=jnp.zeros((F, H), bool),
        # stores (last seq + 1); 0 = never seen (so .max updates work)
        last_seq=jnp.zeros((wf.n_reporters,), jnp.uint32),
        bad_checksum=jnp.zeros((), jnp.uint32),
        seq_anomalies=jnp.zeros((), jnp.uint32),
        received=jnp.zeros((), jnp.uint32),
        lost_reports=jnp.zeros((), jnp.uint32),
    )


def scatter_ref(memory: jax.Array, entry_valid: jax.Array,
                payloads: jax.Array, flow: jax.Array, hist: jax.Array,
                mask: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """Oracle ring placement: memory[flow, hist] = payload (last write wins,
    in report order — matching sequential RDMA WRITEs)."""
    F, H, W = memory.shape
    flat = memory.reshape(F * H, W)
    ev = entry_valid.reshape(F * H)
    idx = jnp.where(mask, flow * H + hist.astype(jnp.int32), F * H)
    flat = flat.at[idx].set(payloads, mode="drop")
    ev = ev.at[idx].set(True, mode="drop")
    return flat.reshape(F, H, W), ev.reshape(F, H)


def ingest(state: CollectorState, payloads: jax.Array, mask: jax.Array,
           shard_flow_base, cfg: DFAConfig,
           scatter_fn=None) -> CollectorState:
    """payloads: (R, 16) u32 RoCEv2 bodies routed to this shard.

    ``scatter_fn`` defaults to the ring_scatter kernel family resolved
    through the dispatch registry (cfg.kernel_backend / env override);
    pass ``scatter_ref`` to force the jnp oracle.
    """
    wf = WIRE.resolve(cfg)
    if scatter_fn is None:
        from repro.kernels.ring_scatter.ops import ring_scatter_collector

        def scatter_fn(memory, entry_valid, pays, flow, hist, m):
            return ring_scatter_collector(memory, entry_valid, pays, flow,
                                          hist, m, cfg=cfg)

    # validate: checksum, flow range and the §VI-B sequence window, with
    # the counters they feed; place: the ring write of what passed
    with jax.named_scope("validate"):
        p = PROTO.unpack_payload(payloads, wire=wf)
        ok_csum = PROTO.payload_valid(payloads, wire=wf)
        bad = jnp.sum(mask & ~ok_csum)  # corrupted/tampered payloads (§VI-B)
        mask = mask & ok_csum
        local = (p["flow_id"].astype(jnp.int32)
                 - jnp.asarray(shard_flow_base, jnp.int32))
        in_range = (local >= 0) & (local < cfg.flows_per_shard)
        mask = mask & in_range
        # sequence continuity per reporter (last_seq stores seq+1; 0 =
        # reporter never seen). The wrap mask and dup window scale with
        # the schema's seq width — V1 keeps the paper's 8-bit space /
        # 8-deep window, V2's u16 space gets a 2048-deep one. Duplicates
        # are REJECTED before placement (first arrival wins), so a
        # replayed payload with a valid checksum but a stale (reporter,
        # seq) identity can never overwrite ring state.
        n_rep = wf.n_reporters
        rep = p["reporter_id"].astype(jnp.int32)
        seq = p["seq"].astype(jnp.uint32)
        prev = state.last_seq[jnp.clip(rep, 0, n_rep - 1)]
        prev_seq = (prev - 1) & jnp.uint32(wf.seq_mask)
        dup_window = mask & (prev > 0) & (seq <= prev_seq) & (
            prev_seq - seq < jnp.uint32(wf.seq_dup_window)
        )                             # small window => duplicate/replay
        # within-batch duplicates: two rows carrying the same (reporter,
        # seq) identity in one ingest. Sort valid rows by identity key
        # (stable, so equal keys keep arrival order — first arrival
        # wins), mark every non-first member of an equal-key run.
        ident = rep.astype(jnp.uint32) * jnp.uint32(wf.seq_mask + 1) + seq
        o1 = jnp.argsort(ident, stable=True)
        order = o1[jnp.argsort((~mask)[o1], stable=True)]  # valid first
        sk, sm = ident[order], mask[order]
        run = jnp.concatenate([jnp.zeros((1,), bool),
                               (sk[1:] == sk[:-1]) & sm[1:] & sm[:-1]])
        dup_batch = jnp.zeros_like(mask).at[order].set(run)
        dup = dup_window | dup_batch
        mask_ok = mask & ~dup
        anomalies = state.seq_anomalies + jnp.sum(dup).astype(jnp.uint32)
        new_seq = state.last_seq.at[jnp.where(mask_ok, rep, n_rep)].max(
            seq + 1, mode="drop")
        # seq-GAP loss detection (unwrapped regime): per reporter, the
        # window advanced by (new - old) seqs this batch but only `fresh`
        # of them landed — the difference is reports sent on the wire
        # that never arrived (or arrived corrupted and were discarded
        # above).
        fresh = mask_ok & (seq + 1 >= prev)
        cnt = jnp.zeros((n_rep + 1,), jnp.uint32).at[
            jnp.where(fresh, rep, n_rep)].add(1, mode="drop")[:n_rep]
        gap = jnp.sum(new_seq - state.last_seq) - jnp.sum(cnt)
    with jax.named_scope("place"):
        memory, ev = scatter_fn(state.memory, state.entry_valid, payloads,
                                jnp.clip(local, 0, cfg.flows_per_shard - 1),
                                p["hist_idx"].astype(jnp.int32), mask_ok)
    return state._replace(
        memory=memory, entry_valid=ev, last_seq=new_seq,
        bad_checksum=state.bad_checksum + bad.astype(jnp.uint32),
        seq_anomalies=anomalies,
        received=state.received + jnp.sum(mask_ok).astype(jnp.uint32),
        lost_reports=state.lost_reports + gap.astype(jnp.uint32))


def staged_ingest(state: CollectorState, payloads: jax.Array,
                  mask: jax.Array, shard_flow_base, cfg: DFAConfig
                  ) -> CollectorState:
    """The DTA-style comparison path (Fig 3 red): payloads land in a staging
    buffer ("host memory"), then a second pass copies them into the Fig 4
    region ("cudaMemcpyHtoD"). Functionally identical, twice the memory
    traffic — used by the fig9 benchmark to quantify what GDR saves."""
    staging = jnp.array(payloads)                 # explicit extra copy
    staging = staging + jnp.uint32(0)             # defeat CSE/no-op elision
    return ingest(state, staging, mask, shard_flow_base, cfg)


def gather_flow_history(state: CollectorState, local_flow: jax.Array
                        ) -> Tuple[jax.Array, jax.Array]:
    """(flows_q,) -> (flows_q, H, 16) entries + validity (inference input)."""
    return state.memory[local_flow], state.entry_valid[local_flow]


def enrich_flow_history(state: CollectorState, local_flow: jax.Array,
                        cfg: DFAConfig, mask=None, backend=None,
                        variant=None) -> jax.Array:
    """Fused alternative to gather_flow_history + derive: (flows_q,) ->
    (flows_q, derived_dim) f32 straight out of the ring region, routed
    through the kernel dispatch registry (backend + gather variant).
    The (flows_q, H, 16) intermediate never exists in HBM.

    ``local_flow``/``mask`` are the translator's routed coordinates
    (pipeline.RoutedBatch) — the enrich half consumes them as produced by
    the ingest half instead of re-deriving placement; masked-out rows are
    zeroed."""
    from repro.core.enrich import enrich_history
    return enrich_history(state.memory, state.entry_valid, local_flow,
                          cfg, mask=mask, backend=backend, variant=variant)
