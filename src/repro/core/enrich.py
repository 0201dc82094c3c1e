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


def entry_feature_list(s) -> list:
    """Seven f32 Table-I register arrays (any common shape) -> the
    PER_ENTRY derived features, as a list of arrays of that shape.

    Moment identities: mean = S1/n, var = S2/n - mean², skew via S3
    (all on the log*-approximated sums, like Marina's CPU stage). Shared
    by :func:`entry_features` and the Pallas kernel, which works on one
    (H, T) plane per register.
    """
    n = jnp.maximum(s[0], 1.0)
    iat1, iat2, iat3 = s[1], s[2], s[3]
    ps1, ps2, ps3 = s[4], s[5], s[6]

    def moments(s1, s2, s3):
        mean = s1 / n
        var = jnp.maximum(s2 / n - mean ** 2, 0.0)
        std = jnp.sqrt(var)
        cov = std / jnp.maximum(mean, EPS)
        m3 = s3 / n - 3 * mean * var - mean ** 3
        skew = m3 / jnp.maximum(std ** 3, EPS)
        return mean, var, std, cov, skew

    i_mean, i_var, i_std, i_cov, i_skew = moments(iat1, iat2, iat3)
    p_mean, p_var, p_std, p_cov, p_skew = moments(ps1, ps2, ps3)
    duration = jnp.maximum(iat1, 1.0)                    # µs total
    volume = ps1                                         # bytes
    rate_bps = volume * 8.0 / (duration / 1e6 + EPS)
    pps = n / (duration / 1e6 + EPS)
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
    mean_w = feats.sum(1) / nvalid
    # two-pass (masked) variance: E[(x-mean)^2] avoids the E[x^2]-mean^2
    # cancellation, keeping ref and kernel paths within 1e-5 relative
    dev = (feats - mean_w[:, None, :]) * vmask
    var_w = (dev * dev).sum(1) / nvalid
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
