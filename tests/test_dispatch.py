"""Kernel dispatch layer: registry contents, backend-selection precedence,
ref vs interpret equivalence for every family, the env-override contract on
the full dfa_step, and run_periods streaming equivalence."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.compat import make_mesh
from repro.configs import get_dfa_config
from repro.core.pipeline import DFASystem
from repro.data import packets as PK
from repro.kernels import dispatch
from repro.kernels.derived_features.ops import derived_features
from repro.kernels.flash_attention.ops import flash_attention
from repro.kernels.flow_moments.ops import flow_moments
from repro.kernels.gather_enrich.ops import gather_enrich
from repro.kernels.ring_scatter.ops import ring_scatter

J = jnp.asarray
FAMILIES = ("flow_moments", "ring_scatter", "derived_features",
            "gather_enrich", "gather_enrich_hbm", "ingest_update",
            "ingest_update_hbm", "flash_attention")


# -- registry & selection -----------------------------------------------------

def test_registry_carries_all_backends_for_all_families():
    assert set(FAMILIES) <= set(dispatch.families())
    for fam in FAMILIES:
        assert set(dispatch.implementations(fam)) == set(dispatch.BACKENDS)


def test_negotiate_tile():
    assert dispatch.negotiate_tile(256, 512) == 256   # clamp to size
    assert dispatch.negotiate_tile(512, 512) == 512
    assert dispatch.negotiate_tile(300, 128) == 100   # largest divisor
    assert dispatch.negotiate_tile(7, 4) == 1         # prime -> 1
    assert dispatch.negotiate_tile(128, 64) == 64


def test_backend_precedence(monkeypatch):
    cfg = get_dfa_config(reduced=True)
    monkeypatch.delenv(dispatch.ENV_VAR, raising=False)
    # auto on CPU -> ref
    assert dispatch.resolve_backend(None, cfg) == "ref"
    assert dispatch.resolve_backend("auto", cfg) == "ref"
    # config field beats auto
    cfg_i = dataclasses.replace(cfg, kernel_backend="interpret")
    assert dispatch.resolve_backend(None, cfg_i) == "interpret"
    # env beats config
    monkeypatch.setenv(dispatch.ENV_VAR, "ref")
    assert dispatch.resolve_backend(None, cfg_i) == "ref"
    # explicit argument beats env
    assert dispatch.resolve_backend("interpret", cfg_i) == "interpret"
    with pytest.raises(ValueError):
        dispatch.resolve_backend("cuda", cfg)


def test_unknown_family_raises():
    with pytest.raises(KeyError):
        dispatch.lookup("no_such_kernel")


def test_unknown_env_backend_always_raises(monkeypatch):
    """Regression: a typo'd REPRO_KERNEL_BACKEND used to be silently
    ignored whenever the call site passed an explicit backend= (explicit
    wins the precedence fight, so the env value was never validated).
    A malformed env var must raise with the registered backends listed,
    no matter what else is set."""
    cfg = get_dfa_config(reduced=True)
    monkeypatch.setenv(dispatch.ENV_VAR, "cuda")
    for explicit in (None, "auto", "ref", "interpret"):
        with pytest.raises(ValueError) as ei:
            dispatch.resolve_backend(explicit, cfg)
        msg = str(ei.value)
        assert dispatch.ENV_VAR in msg
        for b in dispatch.BACKENDS:
            assert b in msg
    with pytest.raises(ValueError):
        dispatch.lookup("gather_enrich", "ref", cfg)


def test_unknown_cfg_backend_raises(monkeypatch):
    monkeypatch.delenv(dispatch.ENV_VAR, raising=False)
    cfg = dataclasses.replace(get_dfa_config(reduced=True),
                              kernel_backend="vulkan")
    with pytest.raises(ValueError) as ei:
        dispatch.resolve_backend(None, cfg)
    assert "kernel_backend" in str(ei.value)
    # explicit argument still beats a malformed config field (only the
    # env var is validated unconditionally: config is code, env is ops)
    assert dispatch.resolve_backend("ref", cfg) == "ref"


# -- gather_enrich memory-strategy variant ------------------------------------

def test_gather_variant_precedence(monkeypatch):
    cfg = get_dfa_config(reduced=True)
    F, H = cfg.flows_per_shard, cfg.history
    args = (F, H, 64, cfg.derived_dim)
    monkeypatch.delenv(dispatch.GATHER_ENV_VAR, raising=False)
    # auto on the reduced config: ring region fits VMEM -> full
    assert dispatch.resolve_gather_variant(None, cfg, *args) == "full"
    # config field beats auto
    cfg_h = dataclasses.replace(cfg, gather_variant="hbm")
    assert dispatch.resolve_gather_variant(None, cfg_h, *args) == "hbm"
    # env beats config
    monkeypatch.setenv(dispatch.GATHER_ENV_VAR, "full")
    assert dispatch.resolve_gather_variant(None, cfg_h, *args) == "full"
    # explicit argument beats env
    assert dispatch.resolve_gather_variant("hbm", cfg_h, *args) == "hbm"
    # malformed env raises even under an explicit argument
    monkeypatch.setenv(dispatch.GATHER_ENV_VAR, "sram")
    for explicit in (None, "auto", "full", "hbm"):
        with pytest.raises(ValueError) as ei:
            dispatch.resolve_gather_variant(explicit, cfg, *args)
        assert dispatch.GATHER_ENV_VAR in str(ei.value)
        assert "hbm" in str(ei.value)


def test_gather_variant_vmem_budget_heuristic(monkeypatch):
    monkeypatch.delenv(dispatch.GATHER_ENV_VAR, raising=False)
    reduced = get_dfa_config(reduced=True)
    paper = get_dfa_config()
    # reduced ring (256 flows) fits a 16 MB budget; paper ring (2^17
    # flows) cannot -> the Tofino-scale config auto-selects hbm
    assert dispatch.resolve_gather_variant(
        None, reduced, reduced.flows_per_shard, reduced.history, 64,
        reduced.derived_dim) == "full"
    assert dispatch.resolve_gather_variant(
        None, paper, paper.flows_per_shard, paper.history, 512,
        paper.derived_dim) == "hbm"
    # shrinking the budget flips the reduced config to hbm too
    tiny = dataclasses.replace(reduced, vmem_budget_mb=0)
    assert dispatch.resolve_gather_variant(
        None, tiny, tiny.flows_per_shard, tiny.history, 64,
        tiny.derived_dim) == "hbm"
    # the hbm working set is F-independent and under any sane budget
    assert dispatch.gather_vmem_bytes(
        "hbm", 1 << 17, 10, 512, 96) == dispatch.gather_vmem_bytes(
        "hbm", 256, 10, 512, 96)
    assert dispatch.ring_vmem_bytes(1 << 17, 10) > 16 * 2**20


def test_gather_vmem_model_counts_mosaic_padding(monkeypatch):
    """The full kernel pins (F, 16*H) rows and (F, H) validity with each
    minor dim padded to 128 lanes, double-buffered: 160 -> 256 and
    10 -> 128 lanes at H = 10, so 2^12 flows is the last power of two
    that fits 16 MB."""
    monkeypatch.delenv(dispatch.GATHER_ENV_VAR, raising=False)
    assert dispatch.ring_vmem_bytes(1 << 12, 10) == 2 * 4 * (1 << 12) * (
        256 + 128)
    cfg = get_dfa_config()
    D = cfg.derived_dim
    assert dispatch.resolve_gather_variant(None, cfg, 1 << 12, 10, 128,
                                           D) == "full"
    assert dispatch.resolve_gather_variant(None, cfg, 1 << 13, 10, 128,
                                           D) == "hbm"
    # a report tile narrower than a lane tile still occupies 128 lanes
    assert dispatch.gather_vmem_bytes("hbm", 1, 10, 64, D) == \
        dispatch.gather_vmem_bytes("hbm", 1, 10, 128, D)


# -- per-family ref vs interpret equivalence ---------------------------------

def test_flow_moments_ref_vs_interpret(rng):
    cfg = get_dfa_config(reduced=True)
    F, E = cfg.flows_per_shard, 200
    regs = rng.integers(0, 2**31, size=(F, 7)).astype(np.uint32)
    slots = rng.integers(0, F, size=E).astype(np.int32)
    deltas = rng.integers(0, 2**32, size=(E, 7),
                          dtype=np.uint64).astype(np.uint32)
    valid = rng.random(E) > 0.2
    ref = flow_moments(J(regs), J(slots), J(deltas), J(valid),
                       backend="ref", cfg=cfg)
    got = flow_moments(J(regs), J(slots), J(deltas), J(valid),
                       backend="interpret", cfg=cfg)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))


def test_ring_scatter_ref_vs_interpret(rng):
    cfg = get_dfa_config(reduced=True)
    F, H = cfg.flows_per_shard, cfg.history
    mem = rng.integers(0, 2**32, size=(F, H, 16),
                       dtype=np.uint64).astype(np.uint32)
    coords = rng.choice(F * H, size=96, replace=False)
    flow = (coords // H).astype(np.int32)
    hist = (coords % H).astype(np.int32)
    pays = rng.integers(0, 2**32, size=(96, 16),
                        dtype=np.uint64).astype(np.uint32)
    mask = rng.random(96) > 0.25
    ref = ring_scatter(J(mem), J(pays), J(flow), J(hist), J(mask),
                       backend="ref", cfg=cfg)
    got = ring_scatter(J(mem), J(pays), J(flow), J(hist), J(mask),
                       backend="interpret", cfg=cfg)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))


def test_pallas_off_tpu_raises_and_interpret_runs(rng):
    """A 'pallas' request off the TPU refuses instead of falling back to
    the interpreter (a run meant for the chip must not carry on on the
    CPU); 'interpret' stays the CPU route and still runs."""
    assert jax.default_backend() != "tpu"
    assert dispatch.interpret_flag("interpret") is True
    with pytest.raises(RuntimeError, match="'interpret'"):
        dispatch.interpret_flag("pallas")
    cfg = get_dfa_config(reduced=True)
    F, H = cfg.flows_per_shard, cfg.history
    mem = J(np.zeros((F, H, 16), np.uint32))
    pays = J(rng.integers(0, 2**32, size=(8, 16),
                          dtype=np.uint64).astype(np.uint32))
    flow, hist = J(np.arange(8, dtype=np.int32)), J(np.zeros(8, np.int32))
    mask = J(np.ones(8, bool))
    with pytest.raises(RuntimeError, match="compiled Pallas kernels"):
        ring_scatter(mem, pays, flow, hist, mask, backend="pallas", cfg=cfg)
    got = ring_scatter(mem, pays, flow, hist, mask, backend="interpret",
                       cfg=cfg)
    np.testing.assert_array_equal(np.asarray(got)[:8, 0], np.asarray(pays))


def test_derived_features_ref_vs_interpret(rng):
    cfg = get_dfa_config(reduced=True)
    F, H = 128, cfg.history
    entries = rng.integers(0, 2**20, size=(F, H, 16),
                           dtype=np.uint64).astype(np.uint32)
    valid = rng.random((F, H)) > 0.3
    ref = derived_features(J(entries), J(valid), cfg, backend="ref")
    got = derived_features(J(entries), J(valid), cfg, backend="interpret")
    # tile-shaped reduction order shifts a few ulp, amplified by the
    # newest-minus-window-mean cancellation: same 1e-3 bound as the
    # kernel sweep in test_kernels
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=1e-3, atol=1e-3)


def test_gather_enrich_ref_vs_interpret(rng):
    cfg = get_dfa_config(reduced=True)
    F, H, R = cfg.flows_per_shard, cfg.history, 128
    mem = rng.integers(0, 2**20, size=(F, H, 16),
                       dtype=np.uint64).astype(np.uint32)
    ev = rng.random((F, H)) > 0.3
    lf = rng.integers(0, F, size=R).astype(np.int32)
    ref = gather_enrich(J(mem), J(ev), J(lf), cfg, backend="ref")
    got = gather_enrich(J(mem), J(ev), J(lf), cfg, backend="interpret")
    assert got.shape == (R, cfg.derived_dim)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=1e-3, atol=1e-3)


def test_gather_enrich_fused_matches_unfused_composition(rng):
    """The fused op == gather_flow_history + derive_ref (the old path)."""
    from repro.core import collector as COLL
    from repro.core import enrich as ENR
    cfg = get_dfa_config(reduced=True)
    F, H, R = cfg.flows_per_shard, cfg.history, 64
    st = COLL.init_state(cfg)
    mem = rng.integers(0, 2**20, size=(F, H, 16),
                       dtype=np.uint64).astype(np.uint32)
    ev = rng.random((F, H)) > 0.5
    st = st._replace(memory=J(mem), entry_valid=J(ev))
    lf = J(rng.integers(0, F, size=R).astype(np.int32))
    entries, evq = COLL.gather_flow_history(st, lf)
    want = ENR.derive_ref(entries, evq, cfg)
    got = gather_enrich(st.memory, st.entry_valid, lf, cfg,
                        backend="interpret")
    # per-row feature-scale tolerance (test_gather_enrich_equiv's
    # contract): the delta columns are differences of ~1e6 operands, so
    # one ulp of the window mean is ~1e-4 of a small delta elementwise
    got, want = np.asarray(got), np.asarray(want)
    assert got.shape == want.shape
    scale = np.maximum(1.0, np.abs(want).max(axis=-1, keepdims=True))
    assert (np.abs(got - want) / scale).max() <= 1e-5


def test_flash_attention_ref_vs_interpret(rng):
    q = J(rng.standard_normal((4, 32, 16)), jnp.float32)
    k = J(rng.standard_normal((2, 32, 16)), jnp.float32)
    v = J(rng.standard_normal((2, 32, 16)), jnp.float32)
    ref = flash_attention(q, k, v, group=2, causal=True, backend="ref")
    got = flash_attention(q, k, v, group=2, causal=True,
                          backend="interpret")
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=2e-4, atol=2e-4)


# -- whole-pipeline backend contract -----------------------------------------

def _one_step(system, env_backend, monkeypatch):
    if env_backend is None:
        monkeypatch.delenv(dispatch.ENV_VAR, raising=False)
    else:
        monkeypatch.setenv(dispatch.ENV_VAR, env_backend)
    flows = PK.gen_flows(12, seed=7)
    ev = PK.events_for_shards(flows, 0, system.n_shards, 128)
    state = system.init_state()
    with system.mesh:
        # fresh jit per backend: resolution happens at trace time
        out = jax.jit(system.dfa_step)(
            state, {k: jnp.asarray(v) for k, v in ev.items()},
            jnp.uint32(90_000))
    return out.state, out.enriched, out.mask, out.metrics


def test_env_override_interpret_matches_ref_end_to_end(monkeypatch):
    """Acceptance contract: REPRO_KERNEL_BACKEND=interpret produces
    bitwise-equal collector memory and <= 1e-5 enrichment deltas vs ref."""
    cfg = get_dfa_config(reduced=True)
    mesh = make_mesh((1, 1), ("data", "model"))
    system = DFASystem(cfg, mesh)
    st_ref, en_ref, em_ref, m_ref = _one_step(system, "ref", monkeypatch)
    st_int, en_int, em_int, m_int = _one_step(system, "interpret",
                                              monkeypatch)
    np.testing.assert_array_equal(np.asarray(st_int.collector.memory),
                                  np.asarray(st_ref.collector.memory))
    np.testing.assert_array_equal(np.asarray(st_int.collector.entry_valid),
                                  np.asarray(st_ref.collector.entry_valid))
    np.testing.assert_array_equal(np.asarray(st_int.reporter.regs),
                                  np.asarray(st_ref.reporter.regs))
    np.testing.assert_array_equal(np.asarray(em_int), np.asarray(em_ref))
    np.testing.assert_allclose(np.asarray(en_int), np.asarray(en_ref),
                               rtol=1e-5, atol=1e-5)
    for k in m_ref:
        assert int(m_int[k]) == int(m_ref[k]), k


# -- multi-period streaming ---------------------------------------------------

def _period_batches(system, T, events_per_shard=128):
    return PK.period_batches(system.n_shards, T, events_per_shard,
                             n_flows=10, flow_seed=3)


def test_run_periods_matches_sequential_steps():
    """Acceptance contract: run_periods over T=4 periods == 4 sequential
    dfa_step calls (state bitwise, outputs stacked)."""
    cfg = get_dfa_config(reduced=True)
    mesh = make_mesh((1, 1), ("data", "model"))
    system = DFASystem(cfg, mesh)
    T = 4
    events, nows = _period_batches(system, T)
    with system.mesh:
        st_seq = system.init_state()
        step = jax.jit(system.dfa_step)
        outs = []
        for t in range(T):
            ev_t = {k: v[t] for k, v in events.items()}
            o = step(st_seq, ev_t, nows[t])
            st_seq = o.state
            outs.append((o.enriched, o.flow_ids, o.mask, o.metrics))
        streamed = jax.jit(system.run_periods)(
            system.init_state(), events, nows)
        st_str, enr_s, fid_s, em_s, met_s = (
            streamed.state, streamed.enriched, streamed.flow_ids,
            streamed.mask, streamed.metrics)
    for a, b in zip(jax.tree.leaves(st_seq), jax.tree.leaves(st_str)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    for t in range(T):
        enr, fid, em, met = outs[t]
        np.testing.assert_allclose(np.asarray(enr_s[t]), np.asarray(enr),
                                   rtol=1e-6, atol=1e-6)
        np.testing.assert_array_equal(np.asarray(fid_s[t]), np.asarray(fid))
        np.testing.assert_array_equal(np.asarray(em_s[t]), np.asarray(em))
        for k in met:
            assert int(met_s[k][t]) == int(met[k]), (t, k)


def test_run_periods_donated_stream():
    """jit_stream runs with donated state and fixed event_specs shapes."""
    cfg = get_dfa_config(reduced=True)
    mesh = make_mesh((1, 1), ("data", "model"))
    system = DFASystem(cfg, mesh)
    T = 3
    events, nows = _period_batches(system, T)
    sds, _ = system.event_specs(128, periods=T)
    for k, v in events.items():
        assert v.shape == sds[k].shape, k
    with system.mesh:
        stream = system.jit_stream(donate=True)
        state = system.init_state()
        out = stream(state, events, nows)
        enr = out.enriched
        # carry is reusable across invocations (streaming loop shape)
        state = stream(out.state, events, nows).state
    assert enr.shape[0] == T
    assert np.isfinite(np.asarray(enr)).all()


@pytest.mark.multidevice
def test_run_periods_multi_shard():
    """Streaming scan over a (2, 2) mesh: routing + scan compose."""
    cfg = get_dfa_config(reduced=True)
    mesh = make_mesh((2, 2), ("data", "model"))
    system = DFASystem(cfg, mesh)
    T = 2
    events, nows = _period_batches(system, T, events_per_shard=64)
    with system.mesh:
        out = jax.jit(system.run_periods)(
            system.init_state(), events, nows)
        fid, em, met = out.flow_ids, out.mask, out.metrics
    sent = int(np.asarray(met["reports_sent"]).sum())
    recv = int(np.asarray(met["reports_recv"]).sum())
    drop = int(np.asarray(met["bucket_drops"]).sum())
    assert sent == recv + drop
    assert recv > 0
    # every received flow id lives in its owner shard's range
    F = cfg.flows_per_shard
    fid_np, em_np = np.asarray(fid), np.asarray(em)
    rows_per_shard = fid_np.shape[1] // system.n_shards
    for t in range(T):
        for shard in range(system.n_shards):
            rows = slice(shard * rows_per_shard,
                         (shard + 1) * rows_per_shard)
            owners = fid_np[t, rows][em_np[t, rows]] // F
            assert (owners == shard).all(), (t, shard)
