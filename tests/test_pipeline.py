"""End-to-end DFA pipeline: packets -> registers -> reports -> routing ->
ring memory -> enriched features, validated against ground truth."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.compat import make_mesh
from repro.configs import get_dfa_config
from repro.core import protocol as P
from repro.core.pipeline import DFASystem
from repro.data import packets as PK


@pytest.fixture(scope="module")
def system():
    mesh = make_mesh((1, 1), ("data", "model"))
    cfg = get_dfa_config(reduced=True)
    return DFASystem(cfg, mesh)


def test_end_to_end_counts(system, rng):
    cfg = system.cfg
    flows = PK.gen_flows(10, seed=1)
    ev = PK.events_for_shards(flows, 0, system.n_shards, 256)
    state = system.init_state()
    with system.mesh:
        step = jax.jit(system.dfa_step)
        out = step(
            state, {k: jnp.asarray(v) for k, v in ev.items()},
            jnp.uint32(100_000))
        enriched, flow_ids, emask, metrics = (out.enriched, out.flow_ids,
                                              out.mask, out.metrics)
    # ground truth: per-flow packet counts
    slots = np.asarray(__import__("repro.core.reporter",
                                  fromlist=["hash_slot"]).hash_slot(
        jnp.asarray(flows["five_tuple"]), cfg.flows_per_shard))
    emask = np.asarray(emask)
    en = np.asarray(enriched)
    fid = np.asarray(flow_ids)
    got_counts = {int(fid[i]): en[i, 0] for i in range(len(fid))
                  if emask[i]}
    truth = {}
    for i, s in enumerate(np.asarray(ev["five_tuple"])):
        sl = int(np.asarray(__import__("repro.core.reporter",
                                       fromlist=["hash_slot"]).hash_slot(
            jnp.asarray(s), cfg.flows_per_shard)))
        truth[sl] = truth.get(sl, 0) + 1
    for f, c in got_counts.items():
        assert truth.get(f % cfg.flows_per_shard, -1) == c, f
    assert int(metrics["reports_recv"]) == len(got_counts)
    assert int(metrics["bad_checksum"]) == 0


def test_memory_entries_verbatim_payloads(system, rng):
    """Fig-4 property: collector memory rows ARE valid RoCEv2 payloads."""
    flows = PK.gen_flows(6, seed=2)
    ev = PK.events_for_shards(flows, 0, system.n_shards, 128)
    state = system.init_state()
    with system.mesh:
        state = jax.jit(system.dfa_step)(
            state, {k: jnp.asarray(v) for k, v in ev.items()},
            jnp.uint32(50_000)).state
    mem = np.asarray(state.collector.memory)
    ev_valid = np.asarray(state.collector.entry_valid)
    rows = mem[ev_valid]
    assert len(rows) > 0
    # independent recomputation of the rotate-then-xor fold (words 0-13 +
    # pad word 15, each rotated left by its payload position)
    acc = np.zeros(len(rows), np.uint64)
    for w in P.CSUM_COVERED:
        x = rows[:, w].astype(np.uint64)
        k = w % 32
        acc ^= ((x << k) | (x >> ((32 - k) % 32))) & 0xFFFFFFFF
    assert (acc.astype(np.uint32) == rows[:, P.CSUM_WORD]).all()
    assert np.asarray(P.payload_valid(jnp.asarray(rows))).all()


def test_history_accumulates_over_periods(system):
    flows = PK.gen_flows(4, seed=3)
    state = system.init_state()
    with system.mesh:
        step = jax.jit(system.dfa_step)
        for i in range(3):
            ev = PK.events_for_shards(flows, i, system.n_shards, 128)
            out = step(
                state, {k: jnp.asarray(v) for k, v in ev.items()},
                jnp.uint32((i + 1) * 100_000))
            state, metrics = out.state, out.metrics
    ev_valid = np.asarray(state.collector.entry_valid)
    per_flow = ev_valid.sum(axis=1)
    assert per_flow.max() == 3        # 3 monitoring periods -> 3 entries


def test_metrics_are_conserved(system):
    flows = PK.gen_flows(12, seed=4)
    ev = PK.events_for_shards(flows, 0, system.n_shards, 256)
    state = system.init_state()
    with system.mesh:
        out = jax.jit(system.dfa_step)(
            state, {k: jnp.asarray(v) for k, v in ev.items()},
            jnp.uint32(60_000))
        emask, metrics = out.mask, out.metrics
    sent = int(metrics["reports_sent"])
    recv = int(metrics["reports_recv"])
    drop = int(metrics["bucket_drops"])
    assert sent == recv + drop
    assert recv == int(np.asarray(emask).sum())


def test_every_period_ring_and_features_pallas_vs_ref():
    """Pallas kernels (interpret) against the ref backend after EVERY
    period, not only at the end: the collector's ring and validity
    bitwise, the delivered features within 1e-5 of their row's scale (the
    tolerance of test_gather_enrich_equiv). 200 flows through 128 report
    slots over 14 periods: every flow tile is written from period 0, the
    reports due outnumber the slots from period 6, and flows fill all 10
    history entries of their ring and wrap."""
    cfg = get_dfa_config(reduced=True)
    mesh = make_mesh((1, 1), ("data", "model"))
    events, nows = PK.period_batches(1, 14, cfg.event_block, n_flows=200,
                                     flow_seed=3)
    runs = {}
    for backend in ("interpret", "ref"):
        system = DFASystem(dataclasses.replace(cfg, kernel_backend=backend),
                           mesh)
        step = system.jit_step(donate=False)
        state, periods = system.init_state(), []
        with system.mesh:
            for t in range(nows.shape[0]):
                out = step(state, {k: v[t] for k, v in events.items()},
                           nows[t])
                state = out.state
                periods.append(jax.device_get(
                    (state.collector.memory, state.collector.entry_valid,
                     out.mask, out.enriched, out.metrics["reports_due"])))
        runs[backend] = periods
    full_rings, tiles = 0, cfg.flows_per_shard // cfg.flow_tile
    for t, (got, want) in enumerate(zip(runs["interpret"], runs["ref"])):
        mem, ev, mask, feat, due = got
        np.testing.assert_array_equal(mem, want[0], err_msg=f"period {t}")
        np.testing.assert_array_equal(ev, want[1], err_msg=f"period {t}")
        np.testing.assert_array_equal(mask, want[2], err_msg=f"period {t}")
        fin = np.isfinite(want[3][mask])
        np.testing.assert_array_equal(np.isfinite(feat[mask]), fin)
        got_f = np.where(fin, feat[mask], 0.0)
        want_f = np.where(fin, want[3][mask], 0.0)
        scale = np.maximum(1.0, np.abs(want_f).max(axis=-1, keepdims=True))
        err = np.abs(got_f - want_f) / scale
        assert err.max(initial=0.0) <= 1e-5, (t, err.max())
        assert ev.reshape(tiles, -1).any(axis=1).all()
        assert (t < 6) or (int(due) > int(mask.sum()) == cfg.report_capacity)
        full_rings = int(ev.all(axis=1).sum())
    assert full_rings > 50
