"""Per-kernel shape/dtype sweeps vs the pure-jnp oracles (interpret mode)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_dfa_config
from repro.kernels.derived_features.kernel import derived_features_pallas
from repro.kernels.derived_features.ref import derived_features_ref
from repro.kernels.flow_moments.kernel import (EVENT_BLOCK,
                                               flow_moments_pallas)
from repro.kernels.flow_moments.ref import flow_moments_ref
from repro.kernels.ring_scatter.kernel import CHUNK, ring_scatter_pallas
from repro.kernels.ring_scatter.ref import ring_scatter_ref

J = jnp.asarray


@pytest.mark.parametrize("F,E,tile", [
    (64, 16, 16), (128, 100, 32), (256, 256, 64), (256, 300, 128),
    (512, 1000, 512),
])
def test_flow_moments_sweep(rng, F, E, tile):
    regs = rng.integers(0, 2**31, size=(F, 7)).astype(np.uint32)
    slots = rng.integers(0, F, size=E).astype(np.int32)
    deltas = rng.integers(0, 2**32, size=(E, 7),
                          dtype=np.uint64).astype(np.uint32)
    valid = rng.random(E) > 0.15
    got = flow_moments_pallas(regs, slots, deltas, valid, flow_tile=tile)
    want = flow_moments_ref(J(regs), J(slots), J(deltas), J(valid))
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_flow_moments_wraparound(rng):
    """u16-split matmul accumulation must preserve mod-2^32 wraparound."""
    F = 64
    regs = np.full((F, 7), 0xFFFFFF00, np.uint32)
    E = EVENT_BLOCK
    slots = np.zeros(E, np.int32)
    deltas = np.full((E, 7), 0x10, np.uint32)
    valid = np.ones(E, bool)
    got = flow_moments_pallas(regs, slots, deltas, valid, flow_tile=64)
    want = flow_moments_ref(J(regs), J(slots), J(deltas), J(valid))
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_flow_moments_all_invalid(rng):
    regs = rng.integers(0, 100, size=(64, 7)).astype(np.uint32)
    got = flow_moments_pallas(regs, np.zeros(32, np.int32),
                              np.ones((32, 7), np.uint32),
                              np.zeros(32, bool), flow_tile=64)
    np.testing.assert_array_equal(np.asarray(got), regs)


def _scatter_coords(rng, layout, F, H, R, tile):
    """(flow, hist, mask) of R reports laid out as ``layout`` says."""
    n_tiles = F // tile
    if layout == "distinct":      # distinct coordinates, random order
        coords = rng.choice(F * H, size=min(R, F * H), replace=False)
        flow, hist = coords // H, coords % H
        mask = rng.random(len(coords)) > 0.2
    elif layout == "repeats":     # few (flow, hist) of one tile, reused
        flow = tile + rng.integers(0, 4, size=R) * 7 % tile
        hist = rng.integers(0, 3, size=R)
        mask = rng.random(R) > 0.1
    elif layout == "one_tile":    # every report in one tile
        flow = (n_tiles - 2) * tile + rng.integers(0, tile, size=R)
        hist = rng.integers(0, H, size=R)
        mask = np.ones(R, bool)
    elif layout == "empty_tiles":  # only tiles 0, 5 and the last
        t = rng.choice([0, 5, n_tiles - 1], size=R)
        flow = t * tile + rng.integers(0, tile, size=R)
        hist = rng.integers(0, H, size=R)
        mask = rng.random(R) > 0.2
    elif layout == "all_masked":
        flow = rng.integers(0, F, size=R)
        hist = rng.integers(0, H, size=R)
        mask = np.zeros(R, bool)
    elif layout == "masked_edges":  # masked rows clipped to flow 0 / F-1
        flow = rng.integers(0, F, size=R)
        hist = rng.integers(0, H, size=R)
        mask = rng.random(R) > 0.5
        flow[~mask] = np.where(rng.random(int((~mask).sum())) > 0.5,
                               0, F - 1)
    else:                         # "over_ring": R > F * H, repeats
        assert R > F * H
        flow = rng.integers(0, F, size=R)
        hist = rng.integers(0, H, size=R)
        mask = rng.random(R) > 0.2
    return (np.asarray(flow, np.int32), np.asarray(hist, np.int32),
            np.asarray(mask, bool))


def _last_write_wins(mem, pay, flow, hist, mask):
    out = mem.copy()
    for r in np.flatnonzero(mask):
        out[flow[r], hist[r]] = pay[r]
    return out


@pytest.mark.parametrize("F,H,R,tile,layout", [
    pytest.param(32, 10, 16, 32, "distinct", id="32-10-16-32"),
    pytest.param(128, 10, 64, 32, "distinct", id="128-10-64-32"),
    pytest.param(64, 4, 128, 64, "distinct", id="64-4-128-64"),
    pytest.param(512, 10, 700, 32, "distinct", id="random_order"),
    pytest.param(128, 10, 300, 32, "repeats", id="repeats_last_wins"),
    pytest.param(256, 10, CHUNK + 100, 32, "one_tile", id="one_tile"),
    pytest.param(512, 10, 400, 32, "empty_tiles", id="empty_tiles"),
    pytest.param(128, 10, 200, 32, "all_masked", id="all_masked"),
    pytest.param(128, 10, 300, 32, "masked_edges", id="masked_edges"),
    pytest.param(512, 10, 2 * CHUNK + 77, 64, "distinct",
                 id="R_not_chunk_multiple"),
    pytest.param(32, 4, 300, 16, "over_ring", id="R_over_ring"),
])
def test_ring_scatter_sweep(rng, F, H, R, tile, layout):
    mem = rng.integers(0, 2**32, size=(F, H, 16),
                       dtype=np.uint64).astype(np.uint32)
    flow, hist, mask = _scatter_coords(rng, layout, F, H, R, tile)
    R = len(flow)
    pay = rng.integers(0, 2**32, size=(R, 16),
                       dtype=np.uint64).astype(np.uint32)
    pay[:, 0] = np.maximum(pay[:, 0], 1)
    got = ring_scatter_pallas(mem, pay, flow, hist, mask, flow_tile=tile,
                              history=H)
    want = ring_scatter_ref(J(mem), J(pay), J(flow), J(hist), J(mask))
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    np.testing.assert_array_equal(
        np.asarray(got), _last_write_wins(mem, pay, flow, hist, mask))


def test_ring_scatter_duplicate_order(rng):
    """RDMA WRITE ordering: later report to the same address wins."""
    F, H = 32, 10
    mem = np.zeros((F, H, 16), np.uint32)
    pay = np.stack([np.full(16, 1, np.uint32), np.full(16, 2, np.uint32),
                    np.full(16, 3, np.uint32)])
    flow = np.asarray([4, 4, 4], np.int32)
    hist = np.asarray([7, 7, 7], np.int32)
    got = np.asarray(ring_scatter_pallas(mem, pay, flow, hist,
                                         np.ones(3, bool), flow_tile=32,
                                         history=H))
    assert (got[4, 7] == 3).all()


@pytest.mark.parametrize("F,tile", [(64, 64), (128, 64), (256, 128)])
def test_derived_features_sweep(rng, F, tile):
    cfg = get_dfa_config(reduced=True)
    entries = rng.integers(0, 2**20, size=(F, cfg.history, 16),
                           dtype=np.uint64).astype(np.uint32)
    valid = rng.random((F, cfg.history)) > 0.3
    got = derived_features_pallas(entries, valid,
                                  derived_dim=cfg.derived_dim,
                                  flow_tile=tile)
    want = derived_features_ref(J(entries), J(valid), cfg)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-3, atol=1e-3)


def test_kernels_plug_into_reporter(rng):
    """flow_moments as the reporter's accumulate_fn (interpret mode)."""
    from repro.core import reporter as R
    from repro.kernels.flow_moments import ops
    cfg = get_dfa_config(reduced=True)
    keys = rng.integers(1, 2**31, size=(6, 5)).astype(np.uint32)
    fidx = rng.integers(0, 6, size=48)
    ev = {"ts": J(np.sort(rng.integers(0, 5000, 48)).astype(np.uint32)
                  + np.arange(48, dtype=np.uint32)),
          "size": J(rng.integers(40, 1500, 48).astype(np.uint32)),
          "five_tuple": J(keys[fidx]),
          "valid": J(np.ones(48, bool))}
    st_ref = R.ingest(R.init_state(cfg), ev, cfg)
    acc = lambda regs, slots, deltas, valid: ops.flow_moments(
        regs, slots, deltas, valid, force="interpret")
    st_k = R.ingest(R.init_state(cfg), ev, cfg, accumulate_fn=acc)
    np.testing.assert_array_equal(np.asarray(st_ref.regs),
                                  np.asarray(st_k.regs))
