"""Feature enrichment — the collector's CUDA-kernel stage, on TPU (§III-C).

Marina derives ~100 statistical features from the moment sums before
inference; DFA moves that onto accelerator compute ("build derived features
on CUDA cores"). From the seven Table-I registers per history entry we
derive, per entry: means, variances, std-devs, coefficients of variation and
skewness for IAT and PS, volume and rate terms; plus cross-history deltas
and window aggregates — ``derived_dim`` (default 96) float32 features per
flow. The hot loop is the derived_features Pallas kernel; this module is
the jnp reference and the feature definitions (shared by both).
"""
from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import DFAConfig
from repro.core import wire as WIRE

EPS = 1e-6
PER_ENTRY = 18            # features derived per history entry


def u32_to_f32(x: jax.Array) -> jax.Array:
    """u32 -> f32 without an unsigned-to-float conversion (Mosaic has
    none). The high half times 2^16 is exact, so the sum rounds once and
    matches a direct cast bit for bit."""
    x = x.astype(jnp.uint32)
    hi = (x >> 16).astype(jnp.int32).astype(jnp.float32)
    lo = (x & jnp.uint32(0xFFFF)).astype(jnp.int32).astype(jnp.float32)
    return hi * 65536.0 + lo


def _nudge(ma, mb, ea, eb, mq, eq):
    """One rounding step for a quotient ``mq * 2^eq`` of ``ma * 2^ea``
    over ``mb * 2^eb`` (24-bit mantissas, biased exponents): the nearest
    of it and its two neighbours. D = ma * 2^k - mq * mb is the exact
    remainder in units where one step of ``mq`` is ``mb``, formed from
    12-bit limbs so that every product fits in int32."""
    k = ea - eq - eb + 150
    sh = 24 - k
    hi_sh = jnp.maximum(sh, 0)
    x_hi = jnp.where(sh > 0, ma >> hi_sh, ma << jnp.maximum(-sh, 0))
    x_lo = jnp.where(sh > 0, (ma & ((1 << hi_sh) - 1)) << jnp.minimum(k, 23),
                     0)
    q1, q0, b1, b0 = mq >> 12, mq & 0xFFF, mb >> 12, mb & 0xFFF
    mid = q1 * b0 + q0 * b1
    d_hi = x_hi - (q1 * b1 + (mid >> 12))
    d = d_hi * 0x1000000 + x_lo - (((mid & 0xFFF) << 12) + q0 * b0)
    ok = (k >= 22) & (k <= 25) & (jnp.abs(d_hi) <= 4)
    low = mq == 0x800000                   # a power of two: finer step below
    up = ok & (2 * d > mb)
    down = ok & (2 * d << jnp.where(low, 1, 0) < -mb)
    mq = mq + jnp.where(up, 1, 0) - jnp.where(down, 1, 0)
    wrap_up, wrap_down = mq == 0x1000000, mq == 0x7FFFFF
    mq = jnp.where(wrap_up, 0x800000, jnp.where(wrap_down, 0xFFFFFF, mq))
    eq = eq + jnp.where(wrap_up, 1, 0) - jnp.where(wrap_down, 1, 0)
    return mq, eq


@jax.jit                         # eager callers compile it once a shape
def div_rn(a: jax.Array, b: jax.Array) -> jax.Array:
    """f32 ``a / b`` rounded to nearest even, as IEEE division rounds.

    A TPU's f32 division lands one unit in the last place off for some
    operands. The skews below subtract nearly equal moments, so one unit
    in a mean can move a skew by orders of magnitude, and with it
    whether a window's sum of squared skews overflows. So the native
    quotient is checked against the exact remainder, in integers, and
    moved to the nearer neighbour where it is off (twice, for a quotient
    up to two units off). Operands or quotients outside the normal range
    (zeros, infinities, NaNs, subnormals) keep the native quotient,
    except that a quotient rounding past the largest float is
    infinite."""
    a, b = jnp.broadcast_arrays(jnp.asarray(a, jnp.float32),
                                jnp.asarray(b, jnp.float32))
    return round_quotient(a, b, a / b)


def round_quotient(a: jax.Array, b: jax.Array, q: jax.Array) -> jax.Array:
    """:func:`div_rn` from a quotient ``q`` of ``a / b`` that may be up
    to two units in the last place off (f32 arrays of one shape)."""
    ia, ib, iq = (jax.lax.bitcast_convert_type(x, jnp.int32)
                  for x in (a, b, q))
    ea, eb, eq = (ia >> 23) & 0xFF, (ib >> 23) & 0xFF, (iq >> 23) & 0xFF
    ma, mb, mq = ((x & 0x7FFFFF) | 0x800000 for x in (ia, ib, iq))
    normal = ((ea > 0) & (ea < 255) & (eb > 0) & (eb < 255)
              & (eq > 0) & (eq < 255))
    for _ in range(2):
        mq, eq = _nudge(ma, mb, ea, eb, mq, eq)
    bits = (iq & jnp.int32(-2**31)) | jnp.where(
        eq < 255, (eq << 23) | (mq & 0x7FFFFF), 0x7F800000)
    return jnp.where(normal & (eq > 0),
                     jax.lax.bitcast_convert_type(bits, jnp.float32), q)


def _div_stacked(nums: list, dens: list) -> list:
    """:func:`div_rn` of each pair of ``nums`` and ``dens`` (arrays of one
    shape, or scalars) as one division over the stacked operands: one
    copy of the rounding in the program, not one per quotient."""
    ops = jnp.broadcast_arrays(*nums, *dens)
    k = len(nums)
    q = div_rn(jnp.stack(ops[:k]), jnp.stack(ops[k:]))
    return [q[j] for j in range(k)]


def entry_feature_list(s) -> list:
    """Seven f32 Table-I register arrays (any common shape) -> the
    PER_ENTRY derived features, as a list of arrays of that shape.

    Moment identities: mean = S1/n, var = S2/n - mean², skew via S3
    (all on the log*-approximated sums, like Marina's CPU stage). Shared
    by :func:`entry_features` and the Pallas kernel, which works on one
    (H, T) plane per register. Every division rounds as IEEE's
    (:func:`div_rn`).
    """
    n = jnp.maximum(s[0], 1.0)
    duration = jnp.maximum(s[1], 1.0)                    # µs total
    volume = s[4]                                        # bytes
    # two rounds of divisions, each one div_rn over stacked operands
    i_mean, i_s2, i_s3, p_mean, p_s2, p_s3, secs = _div_stacked(
        list(s[1:7]) + [duration], [n] * 6 + [1e6])
    secs = secs + EPS
    i_var = jnp.maximum(i_s2 - i_mean ** 2, 0.0)
    p_var = jnp.maximum(p_s2 - p_mean ** 2, 0.0)
    i_std, p_std = jnp.sqrt(i_var), jnp.sqrt(p_var)
    i_m3 = i_s3 - 3 * i_mean * i_var - i_mean ** 3
    p_m3 = p_s3 - 3 * p_mean * p_var - p_mean ** 3
    i_cov, p_cov, i_skew, p_skew, rate_bps, pps = _div_stacked(
        [i_std, p_std, i_m3, p_m3, volume * 8.0, n],
        [jnp.maximum(i_mean, EPS), jnp.maximum(p_mean, EPS),
         jnp.maximum(i_std ** 3, EPS), jnp.maximum(p_std ** 3, EPS),
         secs, secs])
    return [n, i_mean, i_var, i_std, i_cov, i_skew,
            p_mean, p_var, p_std, p_cov, p_skew,
            volume, rate_bps, pps, duration,
            jnp.log1p(volume), jnp.log1p(rate_bps), jnp.log1p(n)]


def entry_features(stats_u32: jax.Array) -> jax.Array:
    """(…, 7) u32 Table-I registers -> (…, PER_ENTRY) f32 derived
    features (:func:`entry_feature_list`, stacked on the last axis)."""
    s = u32_to_f32(stats_u32)
    return jnp.stack(entry_feature_list([s[..., k] for k in range(7)]),
                     axis=-1)


def derive_ref(memory_entries: jax.Array, entry_valid: jax.Array,
               cfg: DFAConfig) -> jax.Array:
    """(F, H, 16) u32 + (F, H) -> (F, derived_dim) f32 — jnp oracle.

    Layout: newest entry's PER_ENTRY | window mean/std over history of
    [n, iat_mean, ps_mean, rate] | deltas newest-vs-window | zero pad.
    """
    F, H, W = memory_entries.shape
    wf = WIRE.resolve(cfg)
    stats = memory_entries[..., wf.payload_stats_slice].astype(jnp.uint32)
    hist_idx = wf.payload_hist.extract(memory_entries).astype(jnp.int32)
    feats = entry_features(stats)                        # (F, H, PER_ENTRY)
    vmask = entry_valid.astype(jnp.float32)[..., None]
    feats = feats * vmask
    nvalid = jnp.maximum(entry_valid.sum(-1, keepdims=True), 1
                         ).astype(jnp.float32)
    # newest = entry with the largest packet count x recency proxy:
    # ring order isn't timestamped; use hist slot of the latest write =
    # argmax over valid entries of packet count (monotone within a flow)
    count = jnp.where(entry_valid, stats[..., 0], 0)
    newest = jnp.argmax(count, axis=-1)                  # (F,)
    newest_f = jnp.take_along_axis(
        feats, newest[:, None, None].repeat(PER_ENTRY, -1), axis=1)[:, 0]
    mean_w = div_rn(feats.sum(1), nvalid)
    # two-pass (masked) variance: E[(x-mean)^2] avoids the E[x^2]-mean^2
    # cancellation, keeping ref and kernel paths within 1e-5 relative
    dev = (feats - mean_w[:, None, :]) * vmask
    var_w = div_rn((dev * dev).sum(1), nvalid)
    std_w = jnp.sqrt(var_w)
    delta = newest_f - mean_w
    maxhist = jnp.max(jnp.where(entry_valid, hist_idx.astype(jnp.float32),
                                0.0), axis=-1, keepdims=True)
    out = jnp.concatenate([newest_f, mean_w, std_w, delta, nvalid,
                           maxhist], axis=-1)
    D = out.shape[-1]
    if D < cfg.derived_dim:
        out = jnp.pad(out, ((0, 0), (0, cfg.derived_dim - D)))
    return out[:, :cfg.derived_dim]


def enrich_history(memory: jax.Array, entry_valid: jax.Array,
                   local_flow: jax.Array, cfg: DFAConfig, mask=None,
                   backend=None, variant=None) -> jax.Array:
    """Selector-routed gather + derivation: the public enrichment entry
    point. (F, H, 16) ring memory + (F, H) validity + (R,) local flow ids
    -> (R, derived_dim) f32.

    Routes through the gather_enrich dispatch family — backend per
    ``DFAConfig.kernel_backend`` / ``REPRO_KERNEL_BACKEND``, memory
    strategy (``full``: ring pinned in VMEM, gathered in the kernel;
    ``hbm``: XLA gathers the R routed rows, the derive kernel streams
    them) per ``DFAConfig.gather_variant`` / ``REPRO_GATHER_VARIANT`` /
    the VMEM-budget heuristic.

    ``mask`` (optional (R,) bool — the routed-report validity from the
    ingest half) zeroes masked-out output rows after the kernel.
    """
    from repro.kernels.gather_enrich.ops import gather_enrich  # no cycle
    out = gather_enrich(memory, entry_valid, local_flow, cfg,
                        backend=backend, variant=variant)
    if mask is not None:
        out = jnp.where(mask[..., None], out, 0.0)
    return out
