"""log* — the paper's lookup-table logarithm (Table I).

Tofino cannot multiply 32-bit values, so Marina/DFA approximate x^n through
pre-populated match-action tables: x -> log*(x), multiply in log domain by
the small integer n (shift/add), and exp* back. We keep the same structure on
TPU: log2 in Q16 fixed point, mantissa refined through a 2^logstar_bits-entry
LUT (the match-action analogue), exp2 through the inverse LUT. All state is
uint32 with natural mod-2^32 wraparound — the P4 register semantics.

Functions are pure jnp (usable inside Pallas kernels and as the oracle).
The ``*_with_lut`` variants take a ``take(lut, idx)`` LUT reader: the
default is a plain gather, which XLA lowers and Mosaic does not; kernel
bodies pass :func:`take_onehot` instead.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

Q = 16                      # fixed-point fractional bits for log values


@functools.lru_cache(maxsize=None)
def _luts(bits: int):
    """(log_lut, exp_lut) as numpy arrays.

    log_lut[i]  = round(2^Q * log2(1 + i/2^bits)),  i in [0, 2^bits)
    exp_lut[i]  = round(2^bits * (2^(i/2^bits) - 1)), i in [0, 2^bits)
    """
    n = 1 << bits
    i = np.arange(n, dtype=np.float64)
    log_lut = np.round((1 << Q) * np.log2(1.0 + i / n)).astype(np.uint32)
    exp_lut = np.round(n * (np.exp2(i / n) - 1.0)).astype(np.uint32)
    return log_lut, exp_lut


def take_gather(lut: jax.Array, idx: jax.Array) -> jax.Array:
    """``lut[idx]`` as a gather (the XLA path)."""
    return lut[idx]


def take_onehot(lut: jax.Array, idx: jax.Array) -> jax.Array:
    """``lut[idx]`` without a gather, for Mosaic kernel bodies.

    ``lut`` is the table as an (n, 1) f32 column, ``idx`` a (1, T) row of
    indices in [0, n). Each lane selects its entry through an (n, T)
    one-hot mask and sums down the sublanes. Exact: every LUT entry is
    below 2^24, so it is an f32 integer, and the sum has one nonzero term.
    Returns a (1, T) u32 row."""
    n = lut.shape[0]
    hot = jax.lax.broadcasted_iota(jnp.int32, (n, idx.shape[-1]), 0) == (
        idx.astype(jnp.int32))
    val = jnp.sum(jnp.where(hot, lut, 0.0), axis=0, keepdims=True)
    return val.astype(jnp.int32).astype(jnp.uint32)


def lut_column(lut) -> jax.Array:
    """An integer LUT as the (n, 1) f32 column :func:`take_onehot` reads."""
    return jnp.asarray(np.asarray(lut, np.float32).reshape(-1, 1))


def log2_star_with_lut(x: jax.Array, bits: int, lut: jax.Array,
                       take=take_gather) -> jax.Array:
    """:func:`log2_star` with the LUT passed explicitly — for Pallas
    kernel bodies, where a captured jnp constant is illegal and the LUT
    must arrive as a kernel input (read through ``take``)."""
    x = x.astype(jnp.uint32)
    # exponent = position of the leading set bit (31 - clz), on u32 so the
    # top bit (x >= 2^31) is handled correctly
    # (a select, not an unsigned max: Mosaic has no unsigned max)
    nbits = (32 - jax.lax.clz(jnp.where(x == 0, jnp.uint32(1), x))).astype(
        jnp.int32)
    e = (nbits - 1).astype(jnp.uint32)                     # floor(log2 x)
    # top `bits` mantissa bits below the leading bit
    shift = jnp.maximum(nbits - 1 - bits, 0).astype(jnp.uint32)
    frac_bits = ((x >> shift) & ((1 << bits) - 1)).astype(jnp.uint32)
    # if the value has fewer than `bits` mantissa bits, scale up
    upshift = jnp.maximum(bits - (nbits - 1), 0).astype(jnp.uint32)
    frac_bits = (frac_bits << upshift) & ((1 << bits) - 1)
    val = (e << Q) + take(lut, frac_bits)
    return jnp.where(x == 0, jnp.uint32(0), val.astype(jnp.uint32))


def log2_star(x: jax.Array, bits: int) -> jax.Array:
    """u32 -> Q16 fixed-point log2 approximation (0 for x == 0)."""
    return log2_star_with_lut(x, bits, jnp.asarray(_luts(bits)[0]))


def exp2_star_with_lut(l: jax.Array, bits: int, lut: jax.Array,
                       take=take_gather) -> jax.Array:
    """:func:`exp2_star` with the LUT passed explicitly (Pallas-safe)."""
    l = l.astype(jnp.uint32)
    e = (l >> Q).astype(jnp.int32)                         # integer part
    frac = ((l >> (Q - bits)) & ((1 << bits) - 1)).astype(jnp.uint32)
    mant = (jnp.uint32(1) << jnp.uint32(bits)) + take(lut, frac)  # [2^b, 2^{b+1})
    sat = e >= 32                       # [2^31, 2^32) is still representable
    sh = jnp.clip(e - bits, -(bits + 32), 31)
    down = jnp.clip(-sh, 1, 31).astype(jnp.uint32)
    # round (not floor) on the down-shift: matters for small values
    rounded = (mant + (jnp.uint32(1) << (down - 1))) >> down
    val = jnp.where(sh >= 0,
                    mant << jnp.clip(sh, 0, 31).astype(jnp.uint32),
                    rounded)
    val = jnp.where(sat, jnp.uint32(0xFFFFFFFF), val)
    return jnp.where(l == 0, jnp.uint32(1), val).astype(jnp.uint32)


def exp2_star(l: jax.Array, bits: int) -> jax.Array:
    """Q16 fixed-point log2 -> u32 value (saturating at 2^32-1)."""
    return exp2_star_with_lut(l, bits, jnp.asarray(_luts(bits)[1]))


def approx_pow_with_luts(x: jax.Array, n: int, bits: int,
                         log_lut: jax.Array, exp_lut: jax.Array,
                         take=take_gather) -> jax.Array:
    """:func:`approx_pow` with both LUTs passed explicitly (Pallas-safe:
    kernel bodies feed the LUT refs they received as inputs)."""
    lx = log2_star_with_lut(x, bits, log_lut, take)
    ln = lx * jnp.uint32(n)
    # detect overflow of the power before exp
    sat = (ln >> Q) >= 32
    v = exp2_star_with_lut(ln, bits, exp_lut, take)
    v = jnp.where(sat, jnp.uint32(0xFFFFFFFF), v)
    return jnp.where(x == 0, jnp.uint32(0), v)


def approx_pow(x: jax.Array, n: int, bits: int) -> jax.Array:
    """x^n through the log*/exp* LUT pipeline (saturating u32); 0 -> 0."""
    log_lut, exp_lut = _luts(bits)
    return approx_pow_with_luts(x, n, bits, jnp.asarray(log_lut),
                                jnp.asarray(exp_lut))


def decode_log(l: jax.Array) -> jax.Array:
    """Q16 log value -> float64-ish float32 2^(l/2^Q) (for enrichment)."""
    return jnp.exp2(l.astype(jnp.float32) / float(1 << Q))
