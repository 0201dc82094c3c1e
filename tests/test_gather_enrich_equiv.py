"""Property-based equivalence suite for the gather_enrich family.

Three implementations must agree on every input:

* ref                — jnp oracle (explicit gather + derive_ref)
* full-block kernel  — ring region pinned in VMEM (interpret mode)
* HBM-resident kernel — ring stays in HBM, only the routed rows are
                       read (interpret mode)

Comparison contract: the two Pallas kernels are BITWISE equal (same
derive_rows math on identically gathered rows), and each matches the ref
oracle to <= 1e-5 relative to the row's feature scale. Elementwise rtol is
the wrong yardstick here: the delta columns are newest-minus-window-mean
differences of ~1e6-magnitude operands, so a single-ulp reduction-order
difference in the mean legitimately lands at ~1e-5 of the *delta* while
being 1e-7 of the quantities actually summed.

Covers: randomized F/H/report_tile/derived_dim (hypothesis), non-power-
of-two R padding, duplicate flow ids inside one tile, all-invalid ring
entries, and the paper-scale F = 2^17, H = 8 acceptance shape.
"""
import dataclasses

import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_dfa_config
from repro.configs.dfa import REDUCED_HBM
from repro.core import collector as COLL
from repro.kernels.gather_enrich.ops import _tile_and_pad, gather_enrich

J = jnp.asarray
STAT_MAX = 1 << 20     # Table-I sums are log*-approximated; bound the
                       # magnitude so float32 feature math stays meaningful


def make_case(rng, F, H, R, invalid_frac=0.3):
    mem = J(rng.integers(0, STAT_MAX, size=(F, H, 16),
                         dtype=np.uint64).astype(np.uint32))
    ev = J(rng.random((F, H)) > invalid_frac)
    lf = J(rng.integers(0, F, size=R).astype(np.int32))
    return mem, ev, lf


def assert_feature_close(got, ref, tol=1e-5):
    """max |got - ref| per row <= tol * that row's feature scale."""
    got, ref = np.asarray(got), np.asarray(ref)
    assert got.shape == ref.shape
    scale = np.maximum(1.0, np.abs(ref).max(axis=-1, keepdims=True))
    err = np.abs(got - ref) / scale
    assert err.max() <= tol, f"scaled err {err.max():.3e} > {tol:g}"


def run_all_three(mem, ev, lf, cfg):
    ref = gather_enrich(mem, ev, lf, cfg, backend="ref")
    full = gather_enrich(mem, ev, lf, cfg, backend="interpret",
                         variant="full")
    hbm = gather_enrich(mem, ev, lf, cfg, backend="interpret",
                        variant="hbm")
    np.testing.assert_array_equal(np.asarray(hbm), np.asarray(full))
    assert_feature_close(full, ref)
    assert_feature_close(hbm, ref)
    return ref


# -- deterministic edge cases -------------------------------------------------

def test_tile_and_pad():
    assert _tile_and_pad(128, 64) == (64, 128)    # exact tiling
    assert _tile_and_pad(100, 64) == (64, 128)    # pad, keep the tile
    assert _tile_and_pad(7, 64) == (7, 7)         # single short tile
    assert _tile_and_pad(300, 128) == (128, 384)
    assert _tile_and_pad(1, 512) == (1, 1)


@pytest.mark.parametrize("R", [1, 7, 100, 128, 300])
def test_non_power_of_two_report_counts(rng, R):
    cfg = get_dfa_config(reduced=True)
    mem, ev, lf = make_case(rng, cfg.flows_per_shard, cfg.history, R)
    ref = run_all_three(mem, ev, lf, cfg)
    assert ref.shape == (R, cfg.derived_dim)


def test_duplicate_flow_ids_in_one_tile(rng):
    """Several reports for the same flow inside one report tile: every
    copy of the row must enrich identically (DMA reads, no writes)."""
    cfg = get_dfa_config(reduced=True)
    F, H = cfg.flows_per_shard, cfg.history
    mem, ev, _ = make_case(rng, F, H, 1)
    lf = J(np.asarray([3, 3, 3, 17, 3, 17, 250, 3] * 8, np.int32))  # R=64=tile
    ref = run_all_three(mem, ev, lf, cfg)
    got = np.asarray(ref)
    rows3 = got[np.asarray(lf) == 3]
    np.testing.assert_array_equal(rows3, np.broadcast_to(rows3[0],
                                                         rows3.shape))


def test_all_invalid_ring_entries(rng):
    """Flows whose entire history ring is invalid: no nan/inf, both
    kernels agree with the oracle's masked-to-zero semantics."""
    cfg = get_dfa_config(reduced=True)
    F, H = cfg.flows_per_shard, cfg.history
    mem, _, lf = make_case(rng, F, H, 64)
    ev = J(np.zeros((F, H), bool))
    ref = run_all_three(mem, ev, lf, cfg)
    assert np.isfinite(np.asarray(ref)).all()


def test_mixed_validity_and_clamped_out_of_range_flows(rng):
    cfg = get_dfa_config(reduced=True)
    F, H = cfg.flows_per_shard, cfg.history
    mem, ev, _ = make_case(rng, F, H, 1)
    lf = J(np.asarray([-5, 0, F - 1, F + 100, 42] * 13, np.int32))  # R=65
    run_all_three(mem, ev, lf, cfg)


def test_paper_scale_f17_h8_hbm_interpret(rng):
    """Acceptance shape: F = 2^17 flows/shard, H = 8 — the ring region
    (~71 MB) can't be a VMEM block; the HBM-resident path must match the
    oracle, and auto-selection must pick it."""
    from repro.kernels import dispatch
    cfg = dataclasses.replace(get_dfa_config(), history=8, flow_tile=128)
    F, H, R = 1 << 17, 8, 256
    assert dispatch.resolve_gather_variant(
        None, cfg, F, H, 128, cfg.derived_dim) == "hbm"
    mem, ev, lf = make_case(rng, F, H, R)
    ref = gather_enrich(mem, ev, lf, cfg, backend="ref")
    hbm = gather_enrich(mem, ev, lf, cfg, backend="interpret")  # auto->hbm
    assert hbm.shape == (R, cfg.derived_dim)
    assert_feature_close(hbm, ref)


def test_collector_enrich_flow_history_routes_fused(rng):
    """collector.enrich_flow_history == gather_flow_history + derive_ref."""
    from repro.core import enrich as ENR
    cfg = REDUCED_HBM
    F, H = cfg.flows_per_shard, cfg.history
    mem, ev, lf = make_case(rng, F, H, 48)
    st = COLL.init_state(cfg)._replace(memory=mem, entry_valid=ev)
    entries, evq = COLL.gather_flow_history(st, lf)
    want = ENR.derive_ref(entries, evq, cfg)
    got = COLL.enrich_flow_history(st, lf, cfg, backend="interpret")
    assert_feature_close(got, want)


# -- randomized sweep (hypothesis; deterministic tests above still run
#    when hypothesis is absent) ----------------------------------------------

try:
    from hypothesis import given, settings
    from hypothesis import strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:       # pragma: no cover - exercised on bare containers
    HAVE_HYPOTHESIS = False

if HAVE_HYPOTHESIS:
    @settings(max_examples=12, deadline=None)
    @given(
        seed=st.integers(0, 2**31 - 1),
        F=st.sampled_from([4, 32, 256, 500]),
        H=st.sampled_from([1, 2, 8, 10]),
        R=st.integers(1, 96),
        report_tile=st.sampled_from([1, 16, 32, 64]),
        derived_dim=st.sampled_from([8, 74, 96, 128]),
        invalid_frac=st.sampled_from([0.0, 0.3, 1.0]),
    )
    def test_equivalence_randomized(seed, F, H, R, report_tile,
                                    derived_dim, invalid_frac):
        cfg = dataclasses.replace(get_dfa_config(reduced=True),
                                  flow_tile=report_tile,
                                  derived_dim=derived_dim)
        rng = np.random.default_rng(seed)
        mem, ev, lf = make_case(rng, F, H, R, invalid_frac)
        ref = run_all_three(mem, ev, lf, cfg)
        assert ref.shape == (R, derived_dim)
