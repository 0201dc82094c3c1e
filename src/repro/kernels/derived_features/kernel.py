"""derived_features — the collector's enrichment stage (Pallas TPU).

The paper runs Marina's ~100 derived-feature computation "on CUDA cores";
here one VPU-bound Pallas kernel decodes the Table-I moment registers of a
tile of T flows into their derived feature block. The math is identical
to repro.core.enrich (the jnp oracle).

Layout. Flows ride the lanes: the kernel reads the collector entries as a
word-major (W*H, T) block (row w*H + h = word w of history entry h) and
writes a (derived_dim, T) block, which the wrapper transposes back to
(T, derived_dim). Every quantity is then an (H, T) plane or a (1, T) row,
which Mosaic lowers without relayouts; the natural (T, H, 16) tile would
pad its 16-word minor dim to 128 lanes. All selection (newest entry) is
iota/one-hot — no gathers — and every reduction over the history adds
rows in order h = 0..H-1, the order XLA's CPU reduce uses, so on the CPU
the kernel and the oracle agree bit for bit.
"""
from __future__ import annotations

import functools
from typing import Callable, List

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core import wire as WIRE
from repro.core.enrich import div_rn, entry_feature_list, u32_to_f32

WORDS = 16


def _hreduce(fn, x: jax.Array) -> jax.Array:
    """(H, T) -> (1, T): ``fn`` folded over the rows in order h = 0..H-1."""
    acc = x[0:1]
    for h in range(1, x.shape[0]):
        acc = fn(acc, x[h:h + 1])
    return acc


def _hsum(x: jax.Array) -> jax.Array:
    return _hreduce(jnp.add, x)


def derive_rows(word: Callable[[int], jax.Array], valid: jax.Array,
                derived_dim: int,
                wire: WIRE.WireFormat = WIRE.V1) -> List[jax.Array]:
    """The feature math shared by this kernel and gather_enrich.

    ``word(w)`` returns word w of every history entry as an (H, T) u32
    plane; ``valid`` is the (H, T) entry validity. Returns derived_dim
    (1, T) f32 rows. Mirrors repro.core.enrich.derive_ref: newest entry's
    PER_ENTRY | window mean | window std | newest - mean | nvalid |
    max hist index | zero pad. Divisions round as IEEE's (``div_rn``).
    """
    H, T = valid.shape
    stats = [u32_to_f32(word(w)) for w in range(*wire.payload_stats)]
    hist = wire.payload_hist.get(word(wire.payload_hist.word)).astype(
        jnp.int32).astype(jnp.float32)            # < 2^16: exact via i32
    vmask = jnp.where(valid, 1.0, 0.0)
    feats = [f * vmask for f in entry_feature_list(stats)]
    nvalid = jnp.maximum(_hsum(valid.astype(jnp.int32)), 1).astype(
        jnp.float32)                              # (1, T)
    # newest = first entry with the largest packet count (argmax), from
    # int32 ops: Mosaic's argmax is f32-only and it has no unsigned
    # compares, so flip the sign bit, which maps u32 order onto i32 order
    count = jnp.where(valid, word(wire.payload_stats[0]), jnp.uint32(0))
    key = (count ^ jnp.uint32(0x80000000)).astype(jnp.int32)
    top = _hreduce(jnp.maximum, key)
    hpos = jax.lax.broadcasted_iota(jnp.int32, (H, T), 0)
    newest = _hreduce(jnp.minimum, jnp.where(key == top, hpos, H))
    sel = jnp.where(hpos == newest, 1.0, 0.0)     # (H, T) one-hot
    newest_f = [_hsum(f * sel) for f in feats]
    # the window means and variances each as one (PER_ENTRY, T) division:
    # whole sublane tiles, and one copy of div_rn, not one per (1, T) row
    mean = div_rn(jnp.concatenate([_hsum(f) for f in feats]), nvalid)
    mean_w = [mean[j:j + 1] for j in range(len(feats))]
    # two-pass (masked) variance — same formulation as enrich.derive_ref
    devs = [(f - m) * vmask for f, m in zip(feats, mean_w)]
    std = jnp.sqrt(div_rn(jnp.concatenate([_hsum(d * d) for d in devs]),
                          nvalid))
    std_w = [std[j:j + 1] for j in range(len(feats))]
    delta = [n - m for n, m in zip(newest_f, mean_w)]
    maxhist = _hreduce(jnp.maximum, jnp.where(valid, hist, 0.0))
    rows = newest_f + mean_w + std_w + delta + [nvalid, maxhist]
    zero = jnp.zeros((1, T), jnp.float32)
    rows += [zero] * (derived_dim - len(rows))
    return rows[:derived_dim]


def write_rows(out_ref, rows: List[jax.Array]) -> None:
    """Store (1, T) rows into a (len(rows), T) output block."""
    for j, row in enumerate(rows):
        out_ref[pl.ds(j, 1), :] = row


def _kernel(ent_ref, valid_ref, out_ref, *, history: int,
            derived_dim: int, wire: WIRE.WireFormat):
    H = history
    write_rows(out_ref, derive_rows(
        lambda w: ent_ref[pl.ds(w * H, H), :], valid_ref[...] > 0,
        derived_dim, wire))


def derive_pallas(entries: jax.Array, valid: jax.Array, *,
                  derived_dim: int, flow_tile: int, interpret: bool,
                  wire: WIRE.WireFormat, name: str) -> jax.Array:
    """The lane-dense derivation kernel over (F, H, 16) entries, tiled by
    ``flow_tile`` flows; ``name`` labels the kernel in the compiled HLO."""
    F, H, W = entries.shape
    assert F % flow_tile == 0 and W == WORDS, (F, flow_tile, W)
    planes = entries.transpose(2, 1, 0).reshape(W * H, F)   # word-major
    out = pl.pallas_call(
        functools.partial(_kernel, history=H, derived_dim=derived_dim,
                          wire=wire),
        grid=(F // flow_tile,),
        in_specs=[
            pl.BlockSpec((W * H, flow_tile), lambda f: (0, f)),
            pl.BlockSpec((H, flow_tile), lambda f: (0, f)),
        ],
        out_specs=pl.BlockSpec((derived_dim, flow_tile), lambda f: (0, f)),
        out_shape=jax.ShapeDtypeStruct((derived_dim, F), jnp.float32),
        interpret=interpret,
        name=name,
    )(planes, valid.astype(jnp.int32).T)
    return out.T


@functools.partial(jax.jit,
                   static_argnames=("derived_dim", "flow_tile", "interpret",
                                    "wire"))
def derived_features_pallas(entries: jax.Array, valid: jax.Array,
                            derived_dim: int = 96, flow_tile: int = 256,
                            interpret: bool = True,
                            wire: WIRE.WireFormat = WIRE.V1) -> jax.Array:
    """entries: (F, H, 16) u32; valid: (F, H) bool -> (F, derived_dim) f32."""
    return derive_pallas(entries, valid, derived_dim=derived_dim,
                         flow_tile=flow_tile, interpret=interpret,
                         wire=wire, name="derived_features")
