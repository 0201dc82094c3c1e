"""ingest_update — fused sort-once, segment-reduce reporter ingest (Pallas).

The multipass ingest processes every event block as ~6 separate jnp
passes: hash -> admit (gather + two scatters) -> resolve_iat (argsort +
inverse argsort) -> event_deltas (a materialized (E, 7) u32 array fed by
four log* pipelines) -> scatter-accumulate -> last_ts scatter. The fused
family keeps the one insight all of those already share — a stable sort
by slot makes each slot's events one contiguous, arrival-ordered run —
and does everything else in a single pass over the sorted stream:

* per-event IAT / first-packet flags fall out of the run boundaries
  (run head reads the last_ts register, everyone else reads the
  in-block predecessor);
* the seven Table-I deltas are formed INLINE and segment-reduced per
  slot run inside the kernel — the per-event (E, 7) delta array exists
  only as a VMEM tile, never in HBM;
* one scatter-add per slot run (plus one scatter-set each for last_ts /
  keys / active) replaces the two-argsorts-plus-three-scatters shape.

Segment reduction is a masked MXU matmul: within one <=256-event tile,
``M[r, r'] = (slot[r'] == slot[r]) & (r' <= r)`` contracts the delta
columns to per-row run-prefix sums; rows selected by the caller (run
tails and tile cuts) carry exact per-(tile-)segment sums. Exactness uses
the flow_moments u16-half trick: u32 deltas split into halves, each
partial sum < 2^24 stays exact in f32, halves recombine mod 2^32.

Two event-stream memory strategies (mirroring gather_enrich):

``ingest_update_pallas`` (block)
    The five sorted stream words are BlockSpec-tiled into VMEM by the
    Pallas pipeline. Right while the stream fits the VMEM budget.

``ingest_update_hbm_pallas`` (HBM-resident)
    The stream stays in HBM (``pl.ANY``); run-boundary metadata (the
    count of non-sentinel rows per tile) is scalar-prefetched into SMEM
    and a double-buffered ``pltpu.make_async_copy`` loop pulls each
    group of event tiles into 2-slot VMEM scratch while the previous
    group's reduction computes. VMEM = O(event_tile) regardless of E, so
    events_per_shard can grow to 2^20; all-pad tiles skip the matmuls.

Variant selection (VMEM-budget heuristic + overrides) lives in
repro.kernels.dispatch; all implementations are bitwise-identical to the
multipass oracle (all-integer math, wrap-safe by construction).
"""
from __future__ import annotations

import functools
from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import logstar as LS

N_REG = 7
REG_PAD = 8              # lane-friendly padded register count
MAX_EVENT_TILE = 256     # u16-half partial sums stay exact in f32


def clamp_tile(event_tile: int, events: int) -> int:
    """Largest legal tile: <= the exactness bound, <= the block size."""
    return max(1, min(int(event_tile), MAX_EVENT_TILE, int(events)))


class SortedStream(NamedTuple):
    """The one-sort product every fused engine consumes. All arrays are
    padded to ``n_tiles * tile`` rows; pad/invalid rows live in the
    sentinel slot F at the tail of the sort order and are dropped by the
    sentinel-index scatters in :func:`apply_updates`."""
    s_slot: jax.Array     # (Ep,) i32 — slot, F = invalid/pad sentinel
    s_ts: jax.Array       # (Ep,) u32 — timestamps (arrival order per run)
    s_ps: jax.Array       # (Ep,) u32 — packet sizes
    s_key: jax.Array      # (Ep, 5) u32 — five-tuples
    base_ts: jax.Array    # (Ep,) u32 — IAT predecessor timestamp
    first: jax.Array      # (Ep,) bool — first packet of a new flow
    head_idx: jax.Array   # (Ep,) i32 — index of the event's run head
    run_tail: jax.Array   # (Ep,) bool — last event of its slot run
    install: jax.Array    # (Ep,) bool — run head claiming an empty slot
    collide: jax.Array    # (Ep,) bool — key mismatch vs resident/installed
    tile: int             # negotiated event tile
    n_events: int         # unpadded E (telemetry only)


def stream_prep(last_ts: jax.Array, keys: jax.Array, active: jax.Array,
                slots: jax.Array, ts: jax.Array, ps: jax.Array,
                five_tuple: jax.Array, valid: jax.Array,
                event_tile: int) -> SortedStream:
    """THE one sort plus the O(E) run-boundary / admission resolution.

    Stable argsort by slot keeps arrival order within a run, which is
    what makes the run head the sequential winner for key install and
    the run tail the wrap-safe last_ts update (see core.reporter)."""
    F = last_ts.shape[0]
    E = slots.shape[0]
    tile = clamp_tile(event_tile, E)
    pad = (-E) % tile
    safe = jnp.where(valid, slots.astype(jnp.int32), F)
    order = jnp.argsort(safe, stable=True)

    def srt(a, c=0):
        out = a[order]
        if pad:
            out = jnp.pad(out, [(0, pad)] + [(0, 0)] * (a.ndim - 1),
                          constant_values=c)
        return out

    s_slot = srt(safe, F)
    s_ts = srt(ts.astype(jnp.uint32))
    s_ps = srt(ps.astype(jnp.uint32))
    s_key = srt(five_tuple.astype(jnp.uint32))
    cl = jnp.clip(s_slot, 0, F - 1)
    reg_last = last_ts[cl]
    reg_active = (s_slot < F) & active[cl]
    reg_key = keys[cl]
    change = s_slot[1:] != s_slot[:-1]
    run_head = jnp.concatenate([jnp.ones((1,), bool), change])
    run_tail = jnp.concatenate([change, jnp.ones((1,), bool)])
    prev_ts = jnp.concatenate([jnp.zeros((1,), s_ts.dtype), s_ts[:-1]])
    base_ts = jnp.where(run_head, reg_last, prev_ts)
    first = run_head & ~reg_active
    # admission in the sorted domain: the run head is the first-come
    # winner; the whole run compares against the resident key (occupied
    # slot) or the head's installed key (previously empty slot)
    idx = jnp.arange(s_slot.shape[0], dtype=jnp.int32)
    head_idx = jax.lax.cummax(jnp.where(run_head, idx, 0))
    eff_key = jnp.where(reg_active[:, None], reg_key, s_key[head_idx])
    match = jnp.all(s_key == eff_key, axis=-1)
    install = run_head & ~reg_active & (s_slot < F)
    collide = (s_slot < F) & ~match & ~install
    return SortedStream(s_slot, s_ts, s_ps, s_key, base_ts, first,
                        head_idx, run_tail, install, collide, tile, E)


def apply_updates(regs: jax.Array, last_ts: jax.Array, keys: jax.Array,
                  active: jax.Array, collisions: jax.Array,
                  st: SortedStream, run_sums: jax.Array,
                  sum_rows: jax.Array
                  ) -> Tuple[jax.Array, jax.Array, jax.Array, jax.Array,
                             jax.Array]:
    """One scatter-add per slot run (``sum_rows`` marks the rows of
    ``run_sums`` carrying a (partial) segment sum) plus the per-slot
    last_ts / keys / active scatter-sets; sentinel indices drop."""
    F = regs.shape[0]
    real = st.s_slot < F
    upd = jnp.where(sum_rows & real, st.s_slot, F)
    regs = regs.at[upd].add(run_sums[:, :N_REG], mode="drop")
    tail = jnp.where(st.run_tail & real, st.s_slot, F)
    last_ts = last_ts.at[tail].set(st.s_ts, mode="drop")
    inst = jnp.where(st.install, st.s_slot, F)
    keys = keys.at[inst].set(st.s_key, mode="drop")
    active = active.at[inst].set(True, mode="drop")
    collisions = collisions + jnp.sum(st.collide).astype(jnp.uint32)
    return regs, last_ts, keys, active, collisions


def delta_cols(iat: jax.Array, ps: jax.Array, bits: int, log_lut,
               exp_lut, take=LS.take_gather):
    """The seven Table-I delta columns (iat already zeroed for firsts).
    The log*/exp* LUTs arrive as arrays so kernel bodies can feed the
    refs they received as inputs (a captured jnp constant is illegal
    inside pallas_call); ``take`` reads them (logstar.take_onehot there)."""
    def pw(x, n):
        return LS.approx_pow_with_luts(x, n, bits, log_lut, exp_lut, take)

    return (jnp.ones_like(ps), iat, pw(iat, 2), pw(iat, 3),
            ps, pw(ps, 2), pw(ps, 3))


def _u32_to_f32_exact(x: jax.Array) -> jax.Array:
    """u32 values below 2^31 -> f32 through int32 (Mosaic has no
    unsigned -> float cast)."""
    return x.astype(jnp.int32).astype(jnp.float32)


def _f32_to_u32_exact(x: jax.Array) -> jax.Array:
    """Integral f32 values below 2^31 -> u32 through int32."""
    return x.astype(jnp.int32).astype(jnp.uint32)


def _tile_sums(slot, ts, ps, base, first, log_lut, exp_lut, *,
               bits: int):
    """(1, tile) sorted inputs -> (8, tile) u32 run-prefix segment sums.

    Column r holds the sum of its run's deltas from the run's first row
    inside this tile through r; run tails / tile cuts are therefore
    exact per-(tile-)segment sums. The work is laid out lane-major (one
    (1, tile) row per quantity), the layout Mosaic lowers without
    relayouts; the LUTs arrive as (n, 1) f32 columns for
    logstar.take_onehot. u16-half matmul keeps u32 exactness (tile <= 256
    -> each half partial sum < 2^24 fits f32; HIGHEST precision keeps
    the MXU from rounding operands to bf16)."""
    tile = slot.shape[-1]
    iat = jnp.where(first != 0, jnp.uint32(0), ts - base)
    d = delta_cols(iat, ps, bits, log_lut, exp_lut, take=LS.take_onehot)
    D = jnp.concatenate(d + (jnp.zeros_like(ps),), axis=0)  # (8, tile)
    lo = _u32_to_f32_exact(D & jnp.uint32(0xFFFF))
    hi = _u32_to_f32_exact(D >> 16)
    # m[a, b] = (slot[a] == slot[b]) & (a <= b): row a feeds column b
    s_b = jnp.broadcast_to(slot, (tile, tile))       # [a, b] = slot[b]
    a = jax.lax.broadcasted_iota(jnp.int32, (tile, tile), 0)
    b = jax.lax.broadcasted_iota(jnp.int32, (tile, tile), 1)
    m = ((s_b == s_b.T) & (a <= b)).astype(jnp.float32)
    hp = jax.lax.Precision.HIGHEST
    acc_lo = jnp.dot(lo, m, precision=hp,
                     preferred_element_type=jnp.float32)
    acc_hi = jnp.dot(hi, m, precision=hp,
                     preferred_element_type=jnp.float32)
    return _f32_to_u32_exact(acc_lo) + (_f32_to_u32_exact(acc_hi) << 16)


# Both variants present the sorted stream to Mosaic as 2D (n_tiles, et)
# arrays, one event tile per row: 1D operands carry XLA's T(1024) memory
# tiling, which neither a 256-event block nor a 256-event DMA slice can
# match. A grid step covers ``group`` rows (GROUP, the 32-bit sublane
# count, or all of them when there are fewer), and the stream is padded
# with sentinel rows to a whole number of groups.

GROUP = 8
N_STREAMS = 5        # slot / ts / ps / base_ts / first


def _group_rows(n_tiles: int) -> int:
    """Tiles per grid step: a full sublane group, or the whole stream."""
    return n_tiles if n_tiles <= GROUP else GROUP


def _stream_rows(stream, et: int):
    """(Ep,) stream words -> (n_tiles, et) rows (XLA-side reshape)."""
    return [a.reshape(-1, et) for a in stream]


def _reduce_group(rows, luts, out_ref, *, group: int, bits: int,
                  live=None):
    """Reduce the ``group`` tiles of one grid step: tile k reads row k of
    each of the five stream refs and writes out_ref[k] = (8, et) sums.
    ``live(k)`` (optional) says whether tile k holds any real event; a
    dead tile writes zeros and skips the matmuls."""
    log_lut, exp_lut = (r[...] for r in luts)

    def tile(k, _):
        def reduce():
            out_ref[k] = _tile_sums(*(r[pl.ds(k, 1), :] for r in rows),
                                    log_lut, exp_lut, bits=bits)
        if live is None:
            reduce()
        else:
            pl.when(live(k))(reduce)

            @pl.when(jnp.logical_not(live(k)))
            def _dead():
                out_ref[k] = jnp.zeros(out_ref.shape[1:], jnp.uint32)
        return 0

    jax.lax.fori_loop(0, group, tile, 0)


def _sums_out(sums: jax.Array) -> jax.Array:
    """(n_tiles, 8, et) kernel output -> (Ep, 8) per-event rows."""
    n, r, et = sums.shape
    return sums.transpose(0, 2, 1).reshape(n * et, r)


# ---------------------------------------------------------------------------
# block variant: sorted stream BlockSpec-tiled through VMEM
# ---------------------------------------------------------------------------

def _block_kernel(slot_ref, ts_ref, ps_ref, base_ref, first_ref,
                  loglut_ref, explut_ref, out_ref, *, group: int,
                  bits: int):
    _reduce_group((slot_ref, ts_ref, ps_ref, base_ref, first_ref),
                  (loglut_ref, explut_ref), out_ref, group=group,
                  bits=bits)


@functools.partial(jax.jit,
                   static_argnames=("bits", "event_tile", "interpret"))
def segment_sums_pallas(s_slot, s_ts, s_ps, base_ts, first_i32, *,
                        bits: int, event_tile: int,
                        interpret: bool = True) -> jax.Array:
    """(Ep,) sorted stream -> (Ep, 8) per-tile-segment sums (block).
    Ep is a whole number of tile groups (see :func:`_group_rows`)."""
    et = event_tile
    rows = _stream_rows((s_slot, s_ts, s_ps, base_ts, first_i32), et)
    n_tiles = rows[0].shape[0]
    group = _group_rows(n_tiles)
    assert n_tiles % group == 0, (n_tiles, group)
    log_lut, exp_lut = (LS.lut_column(t) for t in LS._luts(bits))
    n_lut = 1 << bits
    sums = pl.pallas_call(
        functools.partial(_block_kernel, group=group, bits=bits),
        grid=(n_tiles // group,),
        in_specs=[pl.BlockSpec((group, et), lambda g: (g, 0))] * N_STREAMS
        + [pl.BlockSpec((n_lut, 1), lambda g: (0, 0))] * 2,
        out_specs=pl.BlockSpec((group, REG_PAD, et), lambda g: (g, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((n_tiles, REG_PAD, et), jnp.uint32),
        interpret=interpret,
        name="ingest_update_block",
    )(*rows, log_lut, exp_lut)
    return _sums_out(sums)


# ---------------------------------------------------------------------------
# HBM-resident variant: stream stays in HBM, double-buffered group DMA
# ---------------------------------------------------------------------------

N_SLOTS = 2          # double buffering: fetch group g+1 while g computes


def _hbm_kernel(meta_ref, slot_hbm, ts_hbm, ps_hbm, base_hbm, first_hbm,
                loglut_ref, explut_ref, out_ref, slot_s, ts_s, ps_s,
                base_s, first_s, sems, *, group: int, n_groups: int,
                bits: int):
    """Grid step g: wait for group g's five stream slices (prefetched by
    step g-1, or by the prologue for g == 0), kick off group g+1's DMAs
    into the other scratch slot, then reduce group g. ``meta_ref`` is the
    scalar-prefetched run-boundary metadata: the count of non-sentinel
    rows per tile, so all-pad tiles skip the matmul work entirely."""
    g = pl.program_id(0)
    streams = [(slot_hbm, slot_s), (ts_hbm, ts_s), (ps_hbm, ps_s),
               (base_hbm, base_s), (first_hbm, first_s)]

    def _copies(grp, buf):
        sl = pl.ds(grp * group, group)
        return [pltpu.make_async_copy(hbm.at[sl], scr.at[buf],
                                      sems.at[buf, j])
                for j, (hbm, scr) in enumerate(streams)]

    def start_group(grp, buf):
        for dma in _copies(grp, buf):
            dma.start()

    def wait_group(grp, buf):
        for dma in _copies(grp, buf):
            dma.wait()

    @pl.when(g == 0)
    def _prologue():
        start_group(0, 0)

    @pl.when(g + 1 < n_groups)
    def _prefetch_next():
        start_group(g + 1, (g + 1) % N_SLOTS)

    buf = g % N_SLOTS
    wait_group(g, buf)
    _reduce_group([scr.at[buf] for _, scr in streams],
                  (loglut_ref, explut_ref), out_ref, group=group,
                  bits=bits, live=lambda k: meta_ref[g * group + k] > 0)


@functools.partial(jax.jit,
                   static_argnames=("bits", "event_tile", "interpret"))
def segment_sums_hbm_pallas(tile_nreal, s_slot, s_ts, s_ps, base_ts,
                            first_i32, *, bits: int, event_tile: int,
                            interpret: bool = True) -> jax.Array:
    """Same contract as :func:`segment_sums_pallas`, but the five stream
    arrays never leave HBM as whole blocks: VMEM holds two
    (group, event_tile)-slot scratch sets, so E is unbounded by VMEM.
    ``tile_nreal`` (n_tiles,) i32 is the scalar-prefetched count of
    non-sentinel rows per tile."""
    et = event_tile
    rows = _stream_rows((s_slot, s_ts, s_ps, base_ts, first_i32), et)
    n_tiles = rows[0].shape[0]
    group = _group_rows(n_tiles)
    assert n_tiles % group == 0, (n_tiles, group)
    n_groups = n_tiles // group
    log_lut, exp_lut = (LS.lut_column(t) for t in LS._luts(bits))
    n_lut = 1 << bits
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,            # tile_nreal -> SMEM, whole array
        grid=(n_groups,),
        in_specs=[pl.BlockSpec(memory_space=pl.ANY)] * N_STREAMS
        + [pl.BlockSpec((n_lut, 1), lambda g, meta: (0, 0))] * 2,
        out_specs=pl.BlockSpec((group, REG_PAD, et),
                               lambda g, meta: (g, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((N_SLOTS, group, et), jnp.int32),     # slot
            pltpu.VMEM((N_SLOTS, group, et), jnp.uint32),    # ts
            pltpu.VMEM((N_SLOTS, group, et), jnp.uint32),    # ps
            pltpu.VMEM((N_SLOTS, group, et), jnp.uint32),    # base_ts
            pltpu.VMEM((N_SLOTS, group, et), jnp.int32),     # first
            pltpu.SemaphoreType.DMA((N_SLOTS, N_STREAMS)),
        ],
    )
    sums = pl.pallas_call(
        functools.partial(_hbm_kernel, group=group, n_groups=n_groups,
                          bits=bits),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((n_tiles, REG_PAD, et), jnp.uint32),
        interpret=interpret,
        name="ingest_update_hbm",
    )(tile_nreal, *rows, log_lut, exp_lut)
    return _sums_out(sums)


# ---------------------------------------------------------------------------
# full-contract entry points (what dispatch registers)
# ---------------------------------------------------------------------------

def _fused_pallas(regs, last_ts, keys, active, collisions, slots, ts, ps,
                  five_tuple, valid, *, logstar_bits, event_tile,
                  interpret, hbm):
    F = regs.shape[0]
    st = stream_prep(last_ts, keys, active, slots, ts, ps, five_tuple,
                     valid, event_tile)
    et = st.tile
    Ep = st.s_slot.shape[0]
    # sentinel rows up to a whole number of tile groups (kernel input
    # only: the sums of pad tiles are sliced off before the scatter)
    n_tiles = Ep // et
    extra = ((-n_tiles) % _group_rows(n_tiles)) * et

    def grp_pad(a, c=0):
        return jnp.pad(a, (0, extra), constant_values=c) if extra else a

    stream = (grp_pad(st.s_slot, F), grp_pad(st.s_ts), grp_pad(st.s_ps),
              grp_pad(st.base_ts), grp_pad(st.first.astype(jnp.int32)))
    if hbm:
        n_real = jnp.sum(st.s_slot < F).astype(jnp.int32)
        tile_nreal = jnp.clip(
            n_real - jnp.arange(n_tiles + extra // et, dtype=jnp.int32)
            * et, 0, et)
        sums = segment_sums_hbm_pallas(
            tile_nreal, *stream, bits=logstar_bits, event_tile=et,
            interpret=interpret)
    else:
        sums = segment_sums_pallas(
            *stream, bits=logstar_bits, event_tile=et, interpret=interpret)
    # a run's sum is cut at every tile boundary it crosses; the scatter
    # re-merges the partials (one contributing row per run per tile)
    idx = jnp.arange(Ep, dtype=jnp.int32)
    tile_cut = (idx % et) == (et - 1)
    return apply_updates(regs, last_ts, keys, active, collisions, st,
                         sums[:Ep], st.run_tail | tile_cut)


def ingest_update_pallas(regs, last_ts, keys, active, collisions, slots,
                         ts, ps, five_tuple, valid, *, logstar_bits: int,
                         event_tile: int = MAX_EVENT_TILE,
                         interpret: bool = True):
    """Fused ingest, block event-stream strategy (contract: ref.py)."""
    return _fused_pallas(regs, last_ts, keys, active, collisions, slots,
                         ts, ps, five_tuple, valid,
                         logstar_bits=logstar_bits, event_tile=event_tile,
                         interpret=interpret, hbm=False)


def ingest_update_hbm_pallas(regs, last_ts, keys, active, collisions,
                             slots, ts, ps, five_tuple, valid, *,
                             logstar_bits: int,
                             event_tile: int = MAX_EVENT_TILE,
                             interpret: bool = True):
    """Fused ingest, HBM-resident event-stream strategy."""
    return _fused_pallas(regs, last_ts, keys, active, collisions, slots,
                         ts, ps, five_tuple, valid,
                         logstar_bits=logstar_bits, event_tile=event_tile,
                         interpret=interpret, hbm=True)
