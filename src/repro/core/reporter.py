"""DFA Reporter — line-rate per-flow feature extraction (paper §III-A/IV-A).

State mirrors the Tofino register layout (Fig 7): per flow-slot, eight 32-bit
stateful registers (Table I) plus the report-interval tracking register. The
Marina classification table (five-tuple -> flow id) is adapted to a
device-resident hash-slot table with stored-key collision detection: the
paper's control-plane digest path (<1k flow-mods/s, its acknowledged
bottleneck) is replaced by in-path admission — see DESIGN.md §11(3).

Packet events arrive as time-sorted arrays; IAT resolution uses the stored
last-timestamp register, with in-block predecessors resolved by a stable
sort per slot (the vectorized equivalent of sequential packet processing).
``ingest`` routes through the ingest_update kernel family: the ref backend
keeps this module's multipass shape as the bitwise oracle, the Pallas
backends take the fused sort-once / segment-reduce path
(repro.kernels.ingest_update) that forms the Table-I deltas inside the
kernel and emits one scatter-add per slot run.
"""
from __future__ import annotations

from typing import Any, Dict, NamedTuple, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import DFAConfig
from repro.core import logstar as LS
from repro.core import protocol as PROTO
from repro.core import wire as WIRE

Tree = Any

# register columns (Table I order)
COL_COUNT, COL_IAT, COL_IAT2, COL_IAT3, COL_PS, COL_PS2, COL_PS3 = range(7)
N_REG = 7


class ReporterState(NamedTuple):
    regs: jax.Array        # (F, 7) u32 — Table-I stat registers
    last_ts: jax.Array     # (F,) u32 — last packet timestamp (us)
    last_report: jax.Array  # (F,) u32 — report-interval tracking register
    keys: jax.Array        # (F, 5) u32 — stored five-tuple (admission)
    active: jax.Array      # (F,) bool — slot occupied
    seq: jax.Array         # () u32 — per-reporter sequence counter (VI-B)
    collisions: jax.Array  # () u32 — hash-collision telemetry


def init_state(cfg: DFAConfig) -> ReporterState:
    F = cfg.flows_per_shard
    return ReporterState(
        regs=jnp.zeros((F, N_REG), jnp.uint32),
        last_ts=jnp.zeros((F,), jnp.uint32),
        last_report=jnp.zeros((F,), jnp.uint32),
        keys=jnp.zeros((F, 5), jnp.uint32),
        active=jnp.zeros((F,), bool),
        seq=jnp.zeros((), jnp.uint32),
        collisions=jnp.zeros((), jnp.uint32),
    )


def hash_u32(five_tuple: jax.Array) -> jax.Array:
    """Raw FNV-1a u32 hash of the 5 identity words (no table reduction).

    The full-width hash is the shared key identity both homing schemes
    derive from: ``hash_slot`` masks it into a table, the rendezvous
    scheme mixes it per-node (translator.rendezvous_flow_ids)."""
    h = jnp.full(five_tuple.shape[:-1], 0x811C9DC5, jnp.uint32)
    for i in range(5):
        h = (h ^ five_tuple[..., i].astype(jnp.uint32)) * jnp.uint32(
            0x01000193)
    return h


def hash_slot(five_tuple: jax.Array, n_slots: int) -> jax.Array:
    """FNV-1a style hash of the 5 identity words -> slot index."""
    h = hash_u32(five_tuple)
    if n_slots & (n_slots - 1) == 0:
        # power-of-two table (every shipped config): the modulo is a
        # mask — bit-identical to ``h % n_slots``, no division per event
        return (h & jnp.uint32(n_slots - 1)).astype(jnp.int32)
    return (h % jnp.uint32(n_slots)).astype(jnp.int32)


def event_deltas(iat: jax.Array, ps: jax.Array, first: jax.Array,
                 valid: jax.Array, bits: int) -> jax.Array:
    """Per-event Table-I register deltas (E, 7) u32 via the log* pipeline.

    IAT terms are zero for a flow's first packet (no predecessor)."""
    iat = jnp.where(first, jnp.uint32(0), iat.astype(jnp.uint32))
    ps = ps.astype(jnp.uint32)
    z = jnp.uint32(0)
    d = jnp.stack([
        jnp.ones_like(ps),                       # packet count
        iat,                                     # sum IAT (exact, like P4)
        LS.approx_pow(iat, 2, bits),             # sum IAT^2 (log* approx)
        LS.approx_pow(iat, 3, bits),             # sum IAT^3
        ps,                                      # sum PS
        LS.approx_pow(ps, 2, bits),              # sum PS^2
        LS.approx_pow(ps, 3, bits),              # sum PS^3
    ], axis=-1)
    return jnp.where(valid[..., None], d, z)


def resolve_iat(slots: jax.Array, ts: jax.Array, valid: jax.Array,
                last_ts: jax.Array, active: jax.Array
                ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Per-event (iat, first_flag, new_last_ts).

    Events are time-sorted; a stable sort by slot makes each event's
    predecessor either the previous in-block event of the same slot or the
    register value.
    """
    E = slots.shape[0]
    F = last_ts.shape[0]
    safe_slots = jnp.where(valid, slots, F)      # invalid -> sentinel bucket
    order = jnp.argsort(safe_slots, stable=True)
    s_slot = safe_slots[order]
    s_ts = ts[order]
    prev_same = jnp.concatenate(
        [jnp.array([False]), s_slot[1:] == s_slot[:-1]])
    reg_last = jnp.where(s_slot < F, last_ts[jnp.clip(s_slot, 0, F - 1)], 0)
    reg_active = jnp.where(s_slot < F,
                           active[jnp.clip(s_slot, 0, F - 1)], False)
    prev_ts = jnp.where(prev_same,
                        jnp.concatenate([jnp.zeros((1,), s_ts.dtype),
                                         s_ts[:-1]]), reg_last)
    first = jnp.where(prev_same, False, ~reg_active)
    iat_sorted = (s_ts - prev_ts).astype(jnp.uint32)
    inv = jnp.argsort(order)                      # unsort
    iat = iat_sorted[inv]
    first_flags = first[inv]
    # new last_ts per slot = the LAST event of the slot in arrival order.
    # Events are time-sorted, so that is the latest — but NOT necessarily
    # the numeric max: the u32 µs clock wraps every ~71.6 min, and a
    # ``.max(ts)`` update would pin the stale pre-wrap value forever,
    # corrupting every subsequent IAT. The stable slot-sort keeps arrival
    # order within a slot, so the tail element of each slot run is the
    # wrap-safe update (u32 subtraction in the IAT math already handles
    # the wrap itself).
    run_tail = jnp.concatenate(
        [s_slot[1:] != s_slot[:-1], jnp.array([True])])
    upd = jnp.where(run_tail & (s_slot < F), s_slot, F)
    new_last = last_ts.at[upd].set(s_ts.astype(jnp.uint32), mode="drop")
    return iat, first_flags, new_last


def admit_arrays(keys: jax.Array, active: jax.Array,
                 collisions: jax.Array, slots: jax.Array,
                 five_tuple: jax.Array, valid: jax.Array
                 ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Pure-array hash-slot admission with stored-key collision detection.

    A valid event either (a) matches the stored key (tracked flow),
    (b) lands in an empty slot (new flow — install key), or (c) collides —
    counted in telemetry and the event attributed to the resident flow
    (paper: no explicit mechanism for such flows either, §IV-A).

    First-come install is enforced WITHIN a block too: when several new
    flows hash to the same empty slot in one block, only the first in
    arrival order installs its key; later same-block arrivals compare
    against that installed key (same key -> tracked, different key ->
    collision). The old duplicate-index ``.at[].set`` let the last
    writer win nondeterministically.
    """
    F = keys.shape[0]
    E = slots.shape[0]
    cl = jnp.clip(slots, 0, F - 1)
    stored = keys[cl]                             # (E, 5)
    empty = ~active[cl]
    match = jnp.all(stored == five_tuple, axis=-1) & ~empty
    want_install = valid & empty
    # first arrival index per install slot (scatter-min; sentinel row F)
    cand = jnp.where(want_install, slots, F)
    idx = jnp.arange(E, dtype=jnp.int32)
    first_idx = jnp.full((F + 1,), E, jnp.int32).at[cand].min(idx)
    winner = want_install & (first_idx[cl] == idx)
    tgt = jnp.where(winner, slots, F)             # unique -> deterministic
    new_keys = keys.at[tgt].set(five_tuple, mode="drop")
    new_active = active.at[tgt].set(True, mode="drop")
    # same-block losers compare against the key the winner installed
    dup_match = jnp.all(new_keys[cl] == five_tuple, axis=-1)
    collide = valid & ((~empty & ~match)
                       | (empty & ~winner & ~dup_match))
    new_coll = collisions + jnp.sum(collide).astype(jnp.uint32)
    return new_keys, new_active, new_coll


def admit(state: ReporterState, slots: jax.Array, five_tuple: jax.Array,
          valid: jax.Array) -> Tuple[ReporterState, jax.Array]:
    """State-level wrapper over :func:`admit_arrays` (semantics there)."""
    keys, active, collisions = admit_arrays(
        state.keys, state.active, state.collisions, slots, five_tuple,
        valid)
    return state._replace(keys=keys, active=active,
                          collisions=collisions), valid


def accumulate_ref(regs: jax.Array, slots: jax.Array, deltas: jax.Array,
                   valid: jax.Array) -> jax.Array:
    """Oracle scatter-accumulate (u32 wraparound)."""
    idx = jnp.where(valid, slots, regs.shape[0])
    return regs.at[idx].add(deltas, mode="drop")


def ingest(state: ReporterState, events: Dict[str, jax.Array],
           cfg: DFAConfig, accumulate_fn=None,
           backend=None) -> ReporterState:
    """Process one block of packet events.

    events: ts (E,) u32 µs | size (E,) u32 | five_tuple (E,5) u32 |
            valid (E,) bool

    Routes through the ``ingest_update`` kernel family
    (cfg.kernel_backend / REPRO_KERNEL_BACKEND / ``backend=``): the
    ``ref`` backend keeps the pre-fusion multipass shape (hash -> admit
    -> resolve_iat -> event_deltas -> scatter-accumulate) as the bitwise
    oracle; ``pallas``/``interpret`` take the fused sort-once,
    segment-reduce path (one argsort, deltas formed and reduced per slot
    run inside the kernel, one scatter-add per run). Passing an explicit
    ``accumulate_fn`` forces the legacy multipass path with that
    accumulator (how the flow_moments kernel is unit-tested in place).
    """
    slots = hash_slot(events["five_tuple"], cfg.flows_per_shard)
    if accumulate_fn is not None:
        return _ingest_multipass(state, slots, events, cfg, accumulate_fn)
    from repro.kernels.ingest_update.ops import ingest_update
    regs, last_ts, keys, active, collisions = ingest_update(
        state.regs, state.last_ts, state.keys, state.active,
        state.collisions, slots, events["ts"], events["size"],
        events["five_tuple"], events["valid"], cfg, backend=backend)
    return state._replace(regs=regs, last_ts=last_ts, keys=keys,
                          active=active, collisions=collisions)


def _ingest_multipass(state: ReporterState, slots: jax.Array,
                      events: Dict[str, jax.Array], cfg: DFAConfig,
                      accumulate_fn) -> ReporterState:
    """The pre-fusion multipass ingest with a caller-chosen accumulator
    (admit -> resolve_iat -> event_deltas -> accumulate)."""
    pre_active = state.active            # BEFORE this block's admissions:
    state, valid = admit(state, slots, events["five_tuple"],
                         events["valid"])
    # a flow admitted in this block must see itself as new (first packet)
    iat, first, new_last = resolve_iat(slots, events["ts"], valid,
                                       state.last_ts, pre_active)
    deltas = event_deltas(iat, events["size"], first, valid,
                          cfg.logstar_bits)
    regs = accumulate_fn(state.regs, slots, deltas, valid)
    return state._replace(regs=regs, last_ts=new_last)


def due_mask(state: ReporterState, now: jax.Array,
             cfg: DFAConfig) -> jax.Array:
    """(F,) bool: the active flows whose monitoring period elapsed at
    ``now`` — every flow due, before :func:`due_flows` cuts them to its
    capacity.

    The elapsed compare is u32-subtraction based, so it stays correct
    across µs-clock wrap (now < last_report numerically still yields the
    true elapsed interval mod 2^32).
    """
    elapsed = (now - state.last_report).astype(jnp.uint32)
    return state.active & (elapsed >= jnp.uint32(cfg.monitoring_period_us))


def due_flows(state: ReporterState, now: jax.Array, cfg: DFAConfig,
              capacity: int) -> Tuple[jax.Array, jax.Array]:
    """Flows whose monitoring period elapsed (paper: per-flow configurable
    interval; we use the global default with a per-flow offset hook).

    Returns (slots (capacity,) i32, mask (capacity,) bool) — fixed-size for
    SPMD; selection is by largest elapsed time (most-overdue-first) among
    the :func:`due_mask` flows; the due flows past ``capacity`` wait for a
    later period.
    """
    elapsed = (now - state.last_report).astype(jnp.uint32)
    due = due_mask(state, now, cfg)
    if cfg.monitoring_period_us == 0:
        # elapsed can be 0 for a genuinely due flow; |1 keeps its score
        # above every not-due slot so top_k cannot displace it
        score = jnp.where(due, elapsed | jnp.uint32(1), jnp.uint32(0))
    else:
        score = jnp.where(due, elapsed, jnp.uint32(0))
    # top_k over k > axis size crashes; clamp to F and pad the fixed-size
    # SPMD return back up to ``capacity`` (pad rows masked out)
    F = score.shape[0]
    k = min(capacity, F)
    _, idx = jax.lax.top_k(score, k)
    # gather the due flags at the selected slots — the old ``top > 0``
    # proxy silently dropped genuinely due flows whose elapsed score is 0
    # (monitoring_period_us == 0 reports every period by contract)
    mask = due[idx]
    if k < capacity:
        idx = jnp.concatenate(
            [idx, jnp.zeros((capacity - k,), idx.dtype)])
        mask = jnp.concatenate([mask, jnp.zeros((capacity - k,), bool)])
    return idx.astype(jnp.int32), mask


def make_reports(state: ReporterState, slots: jax.Array, mask: jax.Array,
                 now: jax.Array, reporter_id: int, shard_flow_base,
                 cfg: DFAConfig, flow_ids=None
                 ) -> Tuple[ReporterState, jax.Array]:
    """Clone-and-truncate analogue: emit DTA reports for the given slots.

    Returns (state', reports (capacity, REPORT_WORDS) u32); masked-out rows
    are zero. Sequence numbers increment per report (sec VI-B).

    ``flow_ids`` (optional, (R,) u32) overrides the legacy range identity
    ``shard_flow_base + slot`` — the multi-pod mesh passes the hash-home
    global ids (translator.home_flow_ids of each slot's stored key) so a
    flow's reports name the same home ring from every ingest port.
    """
    R = slots.shape[0]
    stats = state.regs[slots]                     # (R, 7)
    tuples = state.keys[slots]
    if flow_ids is None:
        flow_ids = (shard_flow_base + slots).astype(jnp.uint32)
    else:
        flow_ids = flow_ids.astype(jnp.uint32)
    seqs = state.seq + jnp.cumsum(mask.astype(jnp.uint32)) - 1
    reports = PROTO.pack_dta_report(
        flow_ids, jnp.full((R,), reporter_id, jnp.uint32),
        seqs, stats, tuples, wire=WIRE.resolve(cfg))
    reports = jnp.where(mask[:, None], reports, jnp.uint32(0))
    F = state.last_report.shape[0]
    # wrap-aware: ``now`` is the latest time by contract even when the u32
    # clock wrapped below the stored value, so .set (slots from top_k are
    # unique) — a .max here would stall the interval tracker post-wrap
    last_report = state.last_report.at[jnp.where(mask, slots, F)].set(
        jnp.broadcast_to(now.astype(jnp.uint32), (R,)), mode="drop")
    new_seq = state.seq + jnp.sum(mask).astype(jnp.uint32)
    return state._replace(last_report=last_report, seq=new_seq), reports
