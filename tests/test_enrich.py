"""Derived-feature math: moment identities on exact inputs, and the
IEEE rounding of its divisions."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_dfa_config
from repro.core import enrich as E

J = jnp.asarray


def test_entry_features_moment_identities():
    # synthetic exact sums for x = [2, 4, 6]: n=3, S1=12, S2=56, S3=288
    xs = np.array([2.0, 4.0, 6.0])
    ps = np.array([100.0, 200.0, 300.0])
    stats = jnp.asarray([[3, xs.sum(), (xs**2).sum(), (xs**3).sum(),
                          ps.sum(), (ps**2).sum(), (ps**3).sum()]],
                        jnp.uint32)
    f = np.asarray(E.entry_features(stats))[0]
    assert f[0] == 3
    np.testing.assert_allclose(f[1], xs.mean(), rtol=1e-6)        # iat mean
    np.testing.assert_allclose(f[2], xs.var(), rtol=1e-5)         # iat var
    np.testing.assert_allclose(f[3], xs.std(), rtol=1e-5)
    np.testing.assert_allclose(f[4], xs.std() / xs.mean(), rtol=1e-5)
    np.testing.assert_allclose(f[6], ps.mean(), rtol=1e-6)        # ps mean
    np.testing.assert_allclose(f[11], ps.sum(), rtol=1e-6)        # volume
    # skewness of a symmetric sample is ~0
    m3 = ((xs - xs.mean()) ** 3).mean()
    np.testing.assert_allclose(f[5], m3 / xs.std() ** 3, atol=1e-4)


def test_derive_ref_dims_and_masking():
    cfg = get_dfa_config(reduced=True)
    F, H = 8, cfg.history
    mem = np.zeros((F, H, 16), np.uint32)
    mem[0, 0, 1:8] = [5, 50, 600, 8000, 500, 60000, 7000000]
    valid = np.zeros((F, H), bool)
    valid[0, 0] = True
    out = np.asarray(E.derive_ref(jnp.asarray(mem), jnp.asarray(valid),
                                  cfg))
    assert out.shape == (F, cfg.derived_dim)
    assert np.isfinite(out).all()
    # invalid flows contribute nothing (nvalid column is clamped to >= 1)
    nvalid_col = 4 * E.PER_ENTRY
    masked = np.delete(out[1:], nvalid_col, axis=1)
    assert (masked == 0).all()
    assert out[0, 0] == 5                # count survives the window mean


def _operands(rng, domain, n=200_000):
    """f32 (a, b) pairs whose operands and IEEE quotient are normal."""
    if domain == "bits":
        a, b = (rng.integers(0, 2**32, size=(2, n), dtype=np.uint64)
                .astype(np.uint32).view(np.float32))
    elif domain == "features":    # sums / counts, moments / EPS, S / nvalid
        k = n // 3
        a = np.concatenate([rng.integers(0, 2**32, k).astype(np.float32),
                            (rng.standard_normal(k) * 1e28),
                            3.4e38 * rng.random(k)]).astype(np.float32)
        b = np.concatenate([rng.integers(1, 2**20, k).astype(np.float32),
                            np.full(k, E.EPS),
                            rng.integers(1, 11, k)]).astype(np.float32)
    else:                         # "binade": quotients at powers of two
        a = (2.0 ** rng.integers(-60, 60, n)
             * rng.integers(1, 9, n)).astype(np.float32)
        b = rng.integers(1, 9, n).astype(np.float32)
    with np.errstate(all="ignore"):
        q = a / b
    tiny = np.finfo(np.float32).tiny
    keep = ((np.abs(a) >= tiny) & (np.abs(b) >= tiny) & np.isfinite(a)
            & np.isfinite(b) & (np.abs(q) >= 2 * tiny) & np.isfinite(q))
    return a[keep], b[keep], q[keep]


@pytest.mark.parametrize("ulps", [-2, -1, 0, 1, 2])
@pytest.mark.parametrize("domain", ["bits", "features", "binade"])
def test_round_quotient_is_ieee_division(rng, domain, ulps):
    """A quotient up to two units in the last place off — as a TPU's
    division gives — comes back as IEEE's, bit for bit."""
    a, b, want = _operands(rng, domain)
    off = (want.view(np.int32) + ulps).view(np.float32)
    got = jax.jit(E.round_quotient)(J(a), J(b), J(off))
    np.testing.assert_array_equal(np.asarray(got).view(np.int32),
                                  want.view(np.int32))
    np.testing.assert_array_equal(np.asarray(E.div_rn(J(a), J(b))), want)


def test_div_rn_outside_normal_range():
    """Zeros, infinities, NaNs and subnormals keep the native quotient,
    and a quotient that rounds past the largest float is infinite even
    where the native one stopped a unit short of it."""
    f32 = np.finfo(np.float32)
    vals = np.array([0.0, -0.0, 1.0, -1.5, np.inf, -np.inf, np.nan,
                     f32.tiny, f32.tiny / 4, f32.max, -f32.max],
                    np.float32)
    a, b = (x.ravel() for x in np.meshgrid(vals, vals))
    native = np.asarray(jax.jit(jnp.divide)(J(a), J(b)))
    got = np.asarray(jax.jit(E.div_rn)(J(a), J(b)))
    np.testing.assert_array_equal(np.isnan(got), np.isnan(native))
    ok = ~np.isnan(native)
    np.testing.assert_array_equal(got[ok].view(np.int32),
                                  native[ok].view(np.int32))
    # max / (1 - 2^-24) is 2^128 exactly: IEEE rounds it to infinity
    a = np.array([f32.max, -f32.max], np.float32)
    b = np.full(2, 1 - 2.0**-24, np.float32)
    short = np.array([f32.max, -f32.max], np.float32)
    got = np.asarray(jax.jit(E.round_quotient)(J(a), J(b), J(short)))
    np.testing.assert_array_equal(got, [np.inf, -np.inf])
